"""The Pallas kernel skips the padding blocks of a bucket.

Every dispatch hands the kernel its live count (the well-formed lanes,
packed from lane 0) as an int32 operand; the kernel runs the 1024-lane
blocks that hold a live lane and writes reject to the rest. The dispatch
tests stand a recorder in for the kernel (and for the SHA-512 challenge)
on the CPU; the last test runs the kernel itself in the Pallas
interpreter.
"""

import os

import numpy as np
import pytest

from corda_tpu.crypto import batch_sign, fast_ed25519
from corda_tpu.crypto import provider
from corda_tpu.crypto.provider import VerifyJob
from corda_tpu.obs import trace as obs
from corda_tpu.ops import ed25519_jax, ed25519_pallas, sha512_jax

MALFORMED = 8


@pytest.fixture(params=["native", "no_native"])
def ingest(request, monkeypatch):
    """Both ingest paths of verify_jobs: the native pass over the job
    objects, and the column path (CORDA_TPU_NO_NATIVE=1)."""
    if request.param == "no_native":
        monkeypatch.setenv("CORDA_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(ed25519_jax, "_CPACK_CACHE", [])
    monkeypatch.setattr(provider, "_CVERIFY_CACHE", [])
    has = getattr(ed25519_jax._cpack_module(), "pack_jobs", None) is not None
    if request.param == "native" and not has:
        pytest.skip("no native toolchain/libcrypto")
    return request.param


@pytest.fixture
def kernel(monkeypatch):
    """A TPU as the dispatch sees it: the recorder answers accept on the
    live lanes and reject on the rest, and records (bucket, live)."""
    calls = []

    def record(a, r, s, h, live):
        assert isinstance(live, np.int32)  # an operand, never static
        calls.append((a.shape[1], int(live)))
        out = np.zeros(a.shape[1], bool)
        out[:live] = True
        return out

    ed25519_jax.reset_pallas_state()
    monkeypatch.setitem(ed25519_jax._PALLAS_STATE, "available", True)
    monkeypatch.setattr(ed25519_pallas, "verify_arrays_pallas", record)
    monkeypatch.setattr(sha512_jax, "challenge_words", lambda r, a, m: m)
    yield calls
    ed25519_jax.reset_pallas_state()


def _jobs(n: int, malformed: int = 0, msg_len: int = 32, seed: int = 28):
    """n jobs of random bytes, `malformed` of them with a wrong-length key
    or signature; returns them with the well-formed mask."""
    rng = np.random.default_rng(seed)
    jobs = [VerifyJob(rng.bytes(32), rng.bytes(msg_len), rng.bytes(64))
            for _ in range(n)]
    bad = rng.choice(n, size=malformed, replace=False)
    for k, i in enumerate(bad.tolist()):
        j = jobs[i]
        jobs[i] = (VerifyJob(j.pubkey[:31], j.message, j.sig) if k % 2
                   else VerifyJob(j.pubkey, j.message, j.sig + b"\0"))
    well = np.ones(n, bool)
    well[bad] = False
    return jobs, well


def _dispatch_spans(rec) -> list:
    return [s["attrs"] for s in rec.snapshot()
            if s["name"] == "verify.dispatch"]


def test_verify_jobs_hands_the_kernel_its_well_formed_count(ingest, kernel):
    jobs, well = _jobs(1100, MALFORMED)
    rec = obs.arm("live")
    try:
        got = ed25519_jax.verify_jobs(jobs)
    finally:
        obs.disarm()
    live = 1100 - MALFORMED
    assert kernel == [(4096, live)]  # the bucket of the well-formed count
    assert got.tolist() == well.tolist()  # live verdicts, placed by mask
    assert _dispatch_spans(rec) == [{"lanes": live, "bucket": 4096,
                                     "blocks": 2}]
    assert ed25519_jax.pallas_blocks_run_total() == 2
    assert ed25519_jax.pallas_blocks_skipped_total() == 4096 // 1024 - 2


@pytest.mark.parametrize("msg_len", [32, 40], ids=["device_hash",
                                                    "host_hash"])
def test_verify_batch_hands_the_kernel_its_well_formed_count(kernel,
                                                            msg_len):
    jobs, well = _jobs(1100, MALFORMED, msg_len=msg_len)
    got = ed25519_jax.verify_batch([j.pubkey for j in jobs],
                                   [j.message for j in jobs],
                                   [j.sig for j in jobs])
    assert kernel == [(4096, 1100 - MALFORMED)]
    assert got.tolist() == well.tolist()
    assert ed25519_jax.pallas_blocks_skipped_total() == 2


def test_verify_stream_hands_the_kernel_each_batch_count(kernel):
    batches = []
    for n in (1500, 3000):
        jobs, _ = _jobs(n, seed=n)
        batches.append(([j.pubkey for j in jobs], [j.message for j in jobs],
                        [j.sig for j in jobs]))
    out = list(ed25519_jax.verify_stream(batches))
    assert kernel == [(4096, 1500), (4096, 3000)]
    assert [o.tolist() for o in out] == [[True] * 1500, [True] * 3000]
    assert ed25519_jax.pallas_blocks_run_total() == 2 + 3
    assert ed25519_jax.pallas_blocks_skipped_total() == 2 + 1


def test_a_full_bucket_skips_nothing(kernel):
    jobs, well = _jobs(4096)
    rec = obs.arm("full")
    try:
        assert ed25519_jax.verify_jobs(jobs).all()
    finally:
        obs.disarm()
    assert kernel == [(4096, 4096)]
    assert _dispatch_spans(rec) == [{"lanes": 4096, "bucket": 4096,
                                     "blocks": 4}]
    assert ed25519_jax.pallas_blocks_run_total() == 4
    assert ed25519_jax.pallas_blocks_skipped_total() == 0


def _signed_arrays(n: int, damaged_every: int):
    """n real signatures over distinct keys and tx ids, every
    `damaged_every`-th with a flipped S bit, packed host-hashed into an
    n-lane bucket; returns the word arrays and the true verdicts."""
    rng = np.random.default_rng(n)
    seeds = [rng.bytes(32) for _ in range(n)]
    msgs = [rng.bytes(32) for _ in range(n)]
    sigs = batch_sign.sign_batch(seeds, msgs)
    truth = np.ones(n, bool)
    for i in range(damaged_every - 1, n, damaged_every):
        sigs[i] = sigs[i][:40] + bytes([sigs[i][40] ^ 1]) + sigs[i][41:]
        truth[i] = False
    pks = [fast_ed25519.public_key(s) for s in seeds]
    arrays, _ = ed25519_jax.precompute_batch(pks, msgs, sigs, bucket=n)
    return arrays, truth


def test_the_xla_graph_ignores_live():
    ed25519_jax.reset_pallas_state()
    arrays, truth = _signed_arrays(64, damaged_every=5)
    whole = np.asarray(ed25519_jax.verify_arrays_auto(*arrays))
    part = np.asarray(ed25519_jax.verify_arrays_auto(*arrays, live=5))
    assert ed25519_jax.last_backend() == "xla"
    assert whole.tolist() == part.tolist() == truth.tolist()
    assert ed25519_jax.pallas_blocks_run_total() == 0
    assert ed25519_jax.pallas_blocks_skipped_total() == 0


@pytest.mark.skipif(not os.environ.get("PERFBENCH_INTERPRET"),
                    reason="three blocks of the Pallas kernel in interpret "
                           "mode take ~19 minutes on a CPU; set "
                           "PERFBENCH_INTERPRET=1")
def test_the_kernel_skips_blocks_past_the_live_count_in_interpret_mode():
    """2,048 lanes of real signatures, 700 of them live: the live block
    answers as the whole-batch run does, and the dead block, whose
    signatures are valid, reads reject."""
    arrays, truth = _signed_arrays(2048, damaged_every=9)
    whole = np.asarray(ed25519_pallas.verify_arrays_pallas(
        *arrays, interpret=True))
    part = np.asarray(ed25519_pallas.verify_arrays_pallas(
        *arrays, np.int32(700), interpret=True))
    assert whole.tolist() == truth.tolist()
    assert part[:1024].tolist() == whole[:1024].tolist()
    assert not part[1024:].any()
