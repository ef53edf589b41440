"""The two device tiers share one host half and one device half.

The single chip (`JaxVerifier`) and the mesh (`MeshVerifier`) both pack
through `ed25519_jax.pack` and dispatch through `ed25519_jax.run`; they
differ only in the bucket rule and the graphs. Every verdict is held to
the oracle (`OracleVerifier`); the mesh's bucket to the rule in
ops/buckets.py. Runs on the conftest's 8-device virtual CPU mesh.
"""

import hashlib
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from corda_tpu.crypto import ref_ed25519 as ref
from corda_tpu.crypto.provider import (JaxVerifier, MeshVerifier,
                                       OracleVerifier, VerifyJob)
from corda_tpu.ops import buckets, ed25519_jax, ed25519_pallas, sharded

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs the conftest's 8-device virtual CPU mesh")


def _flip(b: bytes, at: int) -> bytes:
    return b[:at] + bytes([b[at] ^ 0x08]) + b[at + 1:]


def _signed(i: int, msg: bytes | None = None) -> VerifyJob:
    seed = bytes([i + 40]) * 32
    msg = hashlib.sha256(b"tier-%d" % i).digest() if msg is None else msg
    return VerifyJob(ref.public_key(seed), msg, ref.sign(seed, msg))


def _txids() -> list:
    """Tx-id jobs: valid, damaged and malformed lanes."""
    good = [_signed(i) for i in range(4)]
    j = _signed(9)
    return [good[0], VerifyJob(j.pubkey, j.message, _flip(j.sig, 44)),
            good[1], VerifyJob(j.pubkey[:31], j.message, j.sig),
            VerifyJob(j.pubkey, _flip(j.message, 2), j.sig), good[2],
            VerifyJob(j.pubkey, j.message, j.sig + b"\0"), good[3]]


def _inputs(kind: str) -> list:
    if kind == "txids":
        return _txids()
    if kind == "odd_message":  # one 33-byte message: the column path
        return _txids() + [_signed(5, b"a 33-byte message: not a tx id!!")]
    if kind == "bytearray":
        return [VerifyJob(bytearray(j.pubkey), bytearray(j.message),
                          bytearray(j.sig)) for j in _txids()]
    if kind == "all_malformed":
        j = _signed(7)
        return [VerifyJob(j.pubkey[:31], j.message, j.sig),
                VerifyJob(j.pubkey, j.message, j.sig[:63]),
                VerifyJob(b"", b"", b"")]
    assert kind == "empty"
    return []


def _tier(name: str):
    if name == "single_chip":
        return JaxVerifier(device_min_sigs=0)
    return MeshVerifier(n_devices=8, device_min_sigs=0)


@needs_mesh
@pytest.mark.parametrize("kind", ["txids", "odd_message", "bytearray",
                                  "all_malformed", "empty"])
@pytest.mark.parametrize("tier", ["single_chip", "mesh"])
def test_both_tiers_answer_as_the_oracle(tier, kind):
    jobs = _inputs(kind)
    want = OracleVerifier().verify_batch(
        [VerifyJob(bytes(j.pubkey), bytes(j.message), bytes(j.sig))
         for j in jobs])
    v = _tier(tier)
    got = v.verify_batch(jobs)
    assert got.dtype == bool and got.tolist() == want.tolist()
    assert (v.device_batches, v.host_batches) == (int(bool(jobs)), 0)
    if kind == "txids":
        assert want.any() and not want.all()


@needs_mesh
@pytest.mark.parametrize("n_devices, n_good", [
    (8, 1), (8, 19), (8, 65), (3, 19), (3, 1000)])
def test_mesh_bucket_is_an_equal_split_of_the_ladder(n_devices, n_good):
    rng = np.random.default_rng(n_good)
    jobs = [VerifyJob(rng.bytes(32), rng.bytes(32), rng.bytes(64))
            for _ in range(n_good)]
    jobs.insert(n_good // 2, VerifyJob(rng.bytes(31), rng.bytes(32),
                                       rng.bytes(64)))
    packed = sharded.pack(jobs, sharded.make_mesh(n_devices))
    assert (packed.n_good, packed.n_devices) == (n_good, n_devices)
    assert packed.bucket % n_devices == 0
    assert packed.bucket == buckets.pad_to_devices(
        buckets.pick_bucket(n_good), n_devices)
    assert all(a.shape == (8, packed.bucket) for a in packed.arrays)
    assert packed.mask.tolist() == [len(j.pubkey) == 32 for j in jobs]


def test_one_ladder_and_block_size():
    assert ed25519_jax.pick_bucket is buckets.pick_bucket
    assert buckets.BLOCK_LANES == ed25519_pallas.LANES_PER_BLOCK
    assert all(b % buckets.BLOCK_LANES == 0
               for b in buckets.LADDER if b >= buckets.BLOCK_LANES)


def test_the_sidecar_imports_without_jax():
    code = ("import sys, corda_tpu.crypto.sidecar; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                   timeout=120)
