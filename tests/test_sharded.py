"""Multi-chip SPMD verify on the virtual 8-device CPU mesh.

The mesh is the only difference from the single-chip path; accept/reject must
stay bit-identical to the CPU oracle (SURVEY.md §7 hard part #5).  The driver
additionally exercises __graft_entry__.dryrun_multichip out-of-process.
"""

import numpy as np
import pytest

import jax

from corda_tpu.crypto import ref_ed25519 as ref
from corda_tpu.crypto.provider import VerifyJob
from corda_tpu.ops import ed25519_jax, sharded


pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual CPU mesh")


def _sig_fixture(n):
    pks, msgs, sigs = [], [], []
    for i in range(n):
        sk = bytes([(i % 255) + 1]) * 32
        pk = ref.public_key(sk)
        m = b"shard-%d" % i
        s = ref.sign(sk, m)
        if i % 3 == 2:  # corrupt a third: R byte, S byte, or pubkey
            which = i % 9
            if which == 2:
                s = bytes([s[0] ^ 1]) + s[1:]
            elif which == 5:
                s = s[:40] + bytes([s[40] ^ 1]) + s[41:]
            else:
                pk = bytes([pk[0] ^ 1]) + pk[1:]
        pks.append(pk)
        msgs.append(m)
        sigs.append(s)
    return pks, msgs, sigs


def _verify_on_mesh(pks, msgs, sigs, mesh):
    """The mesh tier's two halves: pack on its bucket rule, run its
    graphs."""
    jobs = [VerifyJob(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    return ed25519_jax.run(sharded.pack(jobs, mesh), sharded.graphs(mesh),
                           len(jobs))


def test_sharded_verify_matches_oracle():
    mesh = sharded.make_mesh(8)
    pks, msgs, sigs = _sig_fixture(19)  # ragged: exercises pad-and-mask
    got = _verify_on_mesh(pks, msgs, sigs, mesh)
    want = np.array([ref.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)])
    assert got.tolist() == want.tolist()
    assert want.sum() not in (0, len(want))  # fixture mixes accept and reject


def test_sharded_rejects_malformed_without_raising():
    mesh = sharded.make_mesh(8)
    pks, msgs, sigs = _sig_fixture(4)
    pks[1] = b"\x01" * 7        # wrong-length key
    sigs[2] = b"\x02" * 11      # wrong-length sig
    got = _verify_on_mesh(pks, msgs, sigs, mesh)
    assert got[0] and not got[1] and not got[2]


def test_sharded_device_hash_path_matches_oracle():
    """32-byte messages route the fully-on-device graph (SHA-512 challenge +
    mod-L + verify) through the same mesh; accept set must match the
    oracle, and must match the host-hash sharded path bit-for-bit."""
    mesh = sharded.make_mesh(8)
    pks, msgs, sigs = _sig_fixture(19)
    msgs32 = [m.ljust(32, b".") for m in msgs]
    sigs32 = [ref.sign(bytes([(i % 255) + 1]) * 32, msgs32[i])
              if ref.verify(pks[i], msgs[i], sigs[i]) else sigs[i]
              for i in range(len(sigs))]
    got = _verify_on_mesh(pks, msgs32, sigs32, mesh)
    want = [ref.verify(p, m, s) for p, m, s in zip(pks, msgs32, sigs32)]
    assert got.tolist() == want
    assert any(want) and not all(want)


def test_mesh_verifier_provider_on_mesh():
    # The PRODUCT seam (round-3 VERDICT item 4): MeshVerifier drives the
    # sharded tier through the same BatchVerifier interface every framework
    # call site uses, selectable as verifier = "jax-sharded" in NodeConfig.
    from corda_tpu.crypto.provider import MeshVerifier, VerifyJob, make_verifier

    v = make_verifier("jax-sharded")
    assert isinstance(v, MeshVerifier) and v.name == "jax-sharded"
    # device_min_sigs=0 pins the mesh route (the size crossover would
    # send 21 jobs to the host tier and test nothing sharded).
    v = MeshVerifier(n_devices=8, device_min_sigs=0)
    pks, msgs, sigs = _sig_fixture(21)
    jobs = [VerifyJob(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    got = v.verify_batch(jobs)
    want = [ref.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    assert got.tolist() == want
    assert v.mesh.devices.size == 8
    assert (v.device_batches, v.host_batches) == (1, 0)
    assert v.verify_batch([]).tolist() == []


def test_mesh_verifier_shadow_divergence_raises():
    from corda_tpu.crypto.provider import MeshVerifier, VerifyJob

    v = MeshVerifier(n_devices=8, shadow_rate=1.0, device_min_sigs=0)
    pks, msgs, sigs = _sig_fixture(5)
    jobs = [VerifyJob(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    got = v.verify_batch(jobs)  # agreement: no raise
    assert len(got) == 5


def test_node_config_selects_mesh_verifier(tmp_path):
    # A node flips multi-chip verification on with ONE config line.
    from corda_tpu.node.config import NodeConfig
    from corda_tpu.node.node import Node

    cfg = tmp_path / "node.toml"
    cfg.write_text(
        f'name = "Meshy"\nbase_dir = "{tmp_path}/meshy"\n'
        f'verifier = "jax-sharded"\n')
    node = Node(NodeConfig.load(str(cfg))).start()
    try:
        assert node.smm.verifier.name == "jax-sharded"
        assert node.smm.verifier.mesh.devices.size == len(jax.devices())
    finally:
        node.stop()
