"""The device tier's columnar ingest of a job list.

`ed25519_jax.verify_jobs` packs well-formed lanes straight from the job
objects (native `pack_jobs`) and places verdicts back with one mask; the
provider hands all-Ed25519 batches over whole and splits by scheme only
for mixed ones (`split_batches`). Every verdict is held to the oracle
(`OracleVerifier`), with the native core and with CORDA_TPU_NO_NATIVE=1
(the column path, which stays the behavioural authority).

The CPU's device tier runs the plain XLA graph in 64-lane buckets.
"""

import hashlib
import types

import numpy as np
import pytest

from corda_tpu.crypto import provider
from corda_tpu.crypto import ref_ed25519 as ref
from corda_tpu.crypto.provider import (JaxVerifier, OracleVerifier,
                                       VerifyJob)
from corda_tpu.obs import trace as obs
from corda_tpu.ops import ed25519_jax


def _flip(b: bytes, at: int) -> bytes:
    return b[:at] + bytes([b[at] ^ 0x10]) + b[at + 1:]


def _signed(i: int, msg: bytes | None = None) -> VerifyJob:
    seed = bytes([i + 1]) * 32
    msg = hashlib.sha256(b"tx-%d" % i).digest() if msg is None else msg
    return VerifyJob(ref.public_key(seed), msg, ref.sign(seed, msg))


def _ed_jobs() -> list:
    """Valid lanes, malformed lanes and damaged lanes, all with 32-byte
    messages (tx ids)."""
    good = [_signed(i) for i in range(5)]
    j = _signed(7)
    return [
        good[0],
        VerifyJob(j.pubkey[:31], j.message, j.sig),            # short key
        good[1],
        VerifyJob(j.pubkey, j.message, j.sig + b"\0"),         # long sig
        VerifyJob(b"\xff" * 32, j.message, j.sig),             # 0xff key
        VerifyJob(j.pubkey, j.message, _flip(j.sig, 3)),       # R damaged
        good[2],
        VerifyJob(j.pubkey, j.message, _flip(j.sig, 40)),      # S damaged
        VerifyJob(j.pubkey, _flip(j.message, 17), j.sig),      # tx id damaged
        good[3],
        good[4],
    ]


@pytest.fixture(params=["native", "no_native"])
def native(request, monkeypatch):
    """Both ingest paths: the native core, and CORDA_TPU_NO_NATIVE=1."""
    if request.param == "no_native":
        monkeypatch.setenv("CORDA_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(ed25519_jax, "_CPACK_CACHE", [])
    monkeypatch.setattr(provider, "_CVERIFY_CACHE", [])
    has = getattr(ed25519_jax._cpack_module(), "pack_jobs", None) is not None
    if request.param == "native" and not has:
        pytest.skip("no native toolchain/libcrypto")
    assert has == (request.param == "native")
    return request.param


def _device_verifier() -> JaxVerifier:
    return JaxVerifier(device_min_sigs=0)  # every batch takes the device


def test_verdicts_match_the_oracle(native):
    jobs = _ed_jobs()
    want = OracleVerifier().verify_batch(jobs)
    assert want.tolist() == [True, False, True, False, False, False, True,
                             False, False, True, True]
    v = _device_verifier()
    got = v.verify_batch(jobs)
    assert got.tolist() == want.tolist()
    assert got.flags.writeable
    # A message that is not 32 bytes takes the host-hashed graph.
    odd = jobs + [_signed(9, b"a 20-byte tx message"),
                  VerifyJob(jobs[0].pubkey, b"short", jobs[0].sig)]
    assert v.verify_batch(odd).tolist() == \
        OracleVerifier().verify_batch(odd).tolist()
    # Duck-typed jobs with bytearray and memoryview fields.
    duck = [types.SimpleNamespace(scheme="ed25519",
                                  pubkey=bytearray(j.pubkey),
                                  message=memoryview(j.message), sig=j.sig)
            for j in jobs]
    assert v.verify_batch(duck).tolist() == want.tolist()
    assert (v.device_batches, v.host_batches, v.split_batches) == (3, 0, 0)


def _p256_jobs():
    pytest.importorskip("cryptography")
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.serialization import (Encoding,
                                                              PublicFormat)

    key = ec.derive_private_key(0x2024, ec.SECP256R1())
    pub = key.public_key().public_bytes(Encoding.X962,
                                        PublicFormat.UncompressedPoint)
    msg = b"tls-handshake-blob"
    sig = key.sign(msg, ec.ECDSA(hashes.SHA256()))
    return (VerifyJob(pub, msg, sig, scheme="ecdsa-p256"),
            VerifyJob(pub, b"other", sig, scheme="ecdsa-p256"))


def test_mixed_schemes_split_and_keep_order(native):
    p_ok, p_bad = _p256_jobs()
    ed = _ed_jobs()
    unknown = VerifyJob(ed[0].pubkey, ed[0].message, ed[0].sig,
                        scheme="rsa-4096")
    jobs = [p_ok] + ed[:5] + [p_bad, unknown] + ed[5:] + [p_ok]
    want = OracleVerifier().verify_batch(jobs)
    assert want[0] and want[-1] and not want[6] and not want[7]
    v = _device_verifier()
    assert v.verify_batch(jobs).tolist() == want.tolist()
    assert (v.split_batches, v.device_batches) == (1, 1)
    assert v.verify_batch(ed).tolist() == want[1:6].tolist() \
        + want[8:-1].tolist()
    assert (v.split_batches, v.device_batches) == (1, 2)


def test_empty_and_all_malformed_batches_dispatch_nothing(native,
                                                          monkeypatch):
    def no_dispatch(*a, **k):
        raise AssertionError("a batch with no well-formed lane dispatched")

    monkeypatch.setattr(ed25519_jax, "_dispatch", no_dispatch)
    v = _device_verifier()
    assert v.verify_batch([]).tolist() == []
    j = _signed(3)
    bad = [VerifyJob(j.pubkey[:31], j.message, j.sig),
           VerifyJob(j.pubkey, j.message, j.sig[:63]),
           VerifyJob(b"", b"", b"")]
    got = v.verify_batch(bad)
    assert got.dtype == bool and got.tolist() == [False, False, False]
    assert v.split_batches == 0


def test_bucket_follows_the_well_formed_count(native):
    """64 well-formed lanes and 3 malformed ones: the 64-lane bucket,
    with `lanes` the well-formed count."""
    rng = np.random.default_rng(5)
    jobs = [VerifyJob(rng.bytes(32), rng.bytes(32), rng.bytes(64))
            for _ in range(64)]
    for at, k in ((0, 31), (30, 33), (66, 0)):
        jobs.insert(at, VerifyJob(rng.bytes(k), rng.bytes(32),
                                  rng.bytes(64)))
    rec = obs.arm("columnar")
    try:
        got = _device_verifier().verify_batch(jobs)
    finally:
        obs.disarm()
    assert got.shape == (67,)
    dispatch, = [s for s in rec.snapshot() if s["name"] == "verify.dispatch"]
    assert dispatch["attrs"] == {"lanes": 64, "bucket": 64}
    batch, = [s for s in rec.snapshot() if s["name"] == "verify.batch"]
    assert batch["attrs"] == {"lanes": 67, "split": 0}


def _native_or_skip():
    native = ed25519_jax._cpack_module()
    if getattr(native, "pack_jobs", None) is None:
        pytest.skip("no native toolchain/libcrypto")
    return native


def test_pack_jobs_matches_pack_words_on_compacted_columns():
    native = _native_or_skip()
    jobs = _ed_jobs()
    mask, n_good, bucket, raw = native.pack_jobs(jobs, lambda n: 64)
    well = [len(j.pubkey) == 32 and len(j.sig) == 64 for j in jobs]
    assert np.frombuffer(mask, bool).tolist() == well
    assert (n_good, bucket) == (sum(well), 64)
    good = [j for j, w in zip(jobs, well) if w]
    want = native.pack_words([j.pubkey for j in good],
                             [j.message for j in good],
                             [j.sig for j in good], 64)
    assert list(raw) == list(want)
    # Zero-filled padding lanes, as the device graph expects.
    a = np.frombuffer(raw[0], "<u4").reshape(8, 64)
    assert not a[:, n_good:].any() and a[:, :n_good].any()


def test_pack_jobs_declines_what_only_the_column_path_answers():
    native = _native_or_skip()
    jobs = _ed_jobs()
    p256 = VerifyJob(jobs[0].pubkey, jobs[0].message, jobs[0].sig,
                     scheme="ecdsa-p256")
    long_msg = VerifyJob(jobs[0].pubkey, jobs[0].message + b"!",
                         jobs[0].sig)
    text_key = types.SimpleNamespace(scheme="ed25519", pubkey="k" * 32,
                                     message=jobs[0].message,
                                     sig=jobs[0].sig)
    no_sig = types.SimpleNamespace(scheme="ed25519", pubkey=jobs[0].pubkey,
                                   message=jobs[0].message)
    for odd in (p256, long_msg, text_key, no_sig):
        assert native.pack_jobs(jobs + [odd], lambda n: 64) is None
    # A malformed lane's message is never read: any length packs.
    short_key = VerifyJob(jobs[0].pubkey[:31], b"any length", jobs[0].sig)
    mask, n_good, _, _ = native.pack_jobs(jobs + [short_key], lambda n: 64)
    assert len(mask) == len(jobs) + 1 and mask[-1] == 0


def test_pack_jobs_asks_for_a_bucket_only_with_lanes_to_pack():
    native = _native_or_skip()
    asked = []

    def pick(n):
        asked.append(n)
        return 64

    j = _signed(2)
    assert native.pack_jobs([], pick) == (b"", 0, 0, None)
    assert native.pack_jobs([VerifyJob(j.pubkey[:31], j.message, j.sig)],
                            pick) == (b"\0", 0, 0, None)
    assert asked == []
    assert native.pack_jobs([j] * 3, pick)[1:3] == (3, 64)
    assert asked == [3]
    with pytest.raises(ValueError, match="bucket smaller"):
        native.pack_jobs([j] * 3, lambda n: 2)
