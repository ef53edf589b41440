"""Golden-vector conformance: the JAX kernel vs the Python oracle.

Every case asserts kernel(x) == oracle(x) — the oracle
(corda_tpu/crypto/ref_ed25519.py) defines the authoritative accept set
matching the reference's EdDSAEngine behaviour (reference:
core/src/main/kotlin/net/corda/core/crypto/CryptoUtilities.kt:90-96).
"""

import numpy as np
import pytest

from corda_tpu.crypto import ref_ed25519 as ref
from corda_tpu.ops import ed25519_jax as kernel

rng = np.random.default_rng(99)


def _keypair(i):
    seed = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
    return seed, ref.public_key(seed)


def _flip(b: bytes, idx: int, bit: int = 1) -> bytes:
    out = bytearray(b)
    out[idx] ^= bit
    return bytes(out)


def _run(cases):
    """cases: list of (pk, msg, sig). Assert kernel matches oracle per case."""
    pks = [c[0] for c in cases]
    msgs = [c[1] for c in cases]
    sigs = [c[2] for c in cases]
    got = kernel.verify_batch(pks, msgs, sigs)
    want = [ref.verify(pk, m, s) for pk, m, s in cases]
    assert got.tolist() == want, list(zip(got.tolist(), want))
    return want


def test_valid_signatures_accept():
    cases = []
    for i in range(8):
        seed, pk = _keypair(i)
        msg = bytes(rng.integers(0, 256, int(rng.integers(0, 200)), dtype=np.uint8))
        cases.append((pk, msg, ref.sign(seed, msg)))
    want = _run(cases)
    assert all(want)  # sanity: oracle accepts its own signatures


def test_corruptions_reject_and_match_oracle():
    seed, pk = _keypair(0)
    msg = b"notarise me"
    sig = ref.sign(seed, msg)
    cases = [
        (pk, msg, sig),                       # control: valid
        (pk, msg + b"x", sig),                # message tampered
        (pk, msg, _flip(sig, 0)),             # R corrupted
        (pk, msg, _flip(sig, 40)),            # S corrupted
        (_flip(pk, 3), msg, sig),             # pubkey corrupted
        (pk, b"", sig),                       # wrong (empty) message
        (pk, msg, _flip(sig, 63, 0x80)),      # S high bit set (s >= 2^255)
    ]
    want = _run(cases)
    assert want[0] is True and not any(want[1:])


def test_s_plus_L_accepted_no_range_check():
    # The era's library does not range-check S: s+L verifies the same point.
    seed, pk = _keypair(1)
    msg = b"malleable"
    sig = ref.sign(seed, msg)
    s = int.from_bytes(sig[32:], "little")
    s2 = s + ref.L
    assert s2 < 1 << 256
    sig2 = sig[:32] + s2.to_bytes(32, "little")
    want = _run([(pk, msg, sig2)])
    assert want == [True]


def _small_y_point():
    """A curve point with y < 19, so y+p still fits in 255 bits."""
    for y in range(19):
        x = ref._recover_x(y, 0)
        if x is not None:
            return (x, y)
    raise AssertionError("no small-y point found")


def test_noncanonical_A_encoding_matches_oracle():
    # y >= p in the pubkey encoding: decompression silently reduces mod p.
    pt = _small_y_point()
    pk_canon = ref.compress(pt)
    n = int.from_bytes(pk_canon, "little")
    pk_noncanon = int.to_bytes(n + ref.P, 32, "little")
    msg = b"m"
    # No private key for this point; craft an (invalid) signature and just
    # require kernel == oracle on both encodings.
    sig = bytes(64)
    _run([(pk_canon, msg, sig), (pk_noncanon, msg, sig)])


def test_noncanonical_R_rejected_by_byte_compare():
    seed, pk = _keypair(2)
    msg = b"R games"
    sig = ref.sign(seed, msg)
    r = int.from_bytes(sig[:32], "little")
    if (r & ((1 << 255) - 1)) < 19:  # astronomically unlikely; guard anyway
        pytest.skip("R is a small-y encoding")
    # Perturb R to a non-canonical encoding of the SAME point where possible
    # is not generally doable; instead check that an R with y >= p rejects.
    pt = _small_y_point()
    bad_r = int.to_bytes(int.from_bytes(ref.compress(pt), "little") + ref.P,
                         32, "little")
    sig2 = bad_r + sig[32:]
    want = _run([(pk, msg, sig2)])
    assert want == [False]


def test_invalid_point_rejects():
    # Find a y that is not on the curve.
    for y in range(2, 100):
        if ref._recover_x(y, 0) is None:
            bad_pk = int.to_bytes(y, 32, "little")
            break
    seed, pk = _keypair(3)
    msg = b"x"
    sig = ref.sign(seed, msg)
    want = _run([(bad_pk, msg, sig)])
    assert want == [False]


def test_wrong_lengths_reject_without_raising():
    seed, pk = _keypair(4)
    msg = b"len"
    sig = ref.sign(seed, msg)
    got = kernel.verify_batch([pk[:31], pk, pk], [msg, msg, msg],
                              [sig, sig[:63], sig])
    assert got.tolist() == [False, False, True]


def test_mixed_large_batch():
    cases = []
    for i in range(40):
        seed, pk = _keypair(i)
        msg = bytes([i]) * (i % 7)
        sig = ref.sign(seed, msg)
        if i % 3 == 1:
            sig = _flip(sig, i % 64)
        if i % 5 == 2:
            msg = msg + b"!"
        cases.append((pk, msg, sig))
    _run(cases)


def test_shadow_sampling_detects_kernel_divergence(monkeypatch):
    """SURVEY.md hard part #5: the CPU oracle stays authoritative — a
    diverging kernel result must raise loudly, never pass silently."""
    import numpy as np
    import pytest

    from corda_tpu.crypto import ref_ed25519 as ref
    from corda_tpu.crypto.provider import JaxVerifier, VerifyJob
    from corda_tpu.ops import ed25519_jax

    sk = b"\x17" * 32
    pk = ref.public_key(sk)
    msg = b"shadowed"
    sig = ref.sign(sk, msg)
    jobs = [VerifyJob(pk, msg, sig)]

    # device_min_sigs=0 pins the kernel route: a 1-job batch would
    # otherwise take the host tier, which has no kernel to shadow.
    ok = JaxVerifier(shadow_rate=1.0, device_min_sigs=0).verify_batch(jobs)
    assert ok.tolist() == [True]

    # Sabotage the kernel: flip every verdict. Shadow sampling must catch it.
    real = ed25519_jax.verify_jobs
    monkeypatch.setattr(ed25519_jax, "verify_jobs",
                        lambda *a, **k: ~real(*a, **k))
    with pytest.raises(RuntimeError, match="divergence"):
        JaxVerifier(shadow_rate=1.0, device_min_sigs=0).verify_batch(jobs)


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_verify_stream_matches_oracle_across_batches(depth):
    """The stream pipeline must return per-batch results in order at every
    pipeline depth, bit-identical to the oracle, including mixed
    valid/invalid rows and varying batch sizes."""
    from corda_tpu.crypto import ref_ed25519 as ref
    from corda_tpu.ops import ed25519_jax

    batches, expects = [], []
    for b, size in enumerate((5, 9, 3)):
        pks, msgs, sigs, expect = [], [], [], []
        for i in range(size):
            sk = bytes([b * 16 + i + 1]) * 32
            pk = ref.public_key(sk)
            m = b"stream-%d-%d" % (b, i)
            s = ref.sign(sk, m)
            ok = (i + b) % 3 != 2
            if not ok:
                s = s[:7] + bytes([s[7] ^ 0x20]) + s[8:]
            pks.append(pk)
            msgs.append(m)
            sigs.append(s)
            expect.append(ok)
        batches.append((pks, msgs, sigs))
        expects.append(expect)

    outs = list(ed25519_jax.verify_stream(iter(batches), bucket=16,
                                      depth=depth))
    assert [o.tolist() for o in outs] == expects


def test_device_hash_path_matches_oracle_for_txid_messages():
    """32-byte messages (tx ids) route through the fully-on-device path
    (SHA-512 challenge + sc_reduce on device, ops/sha512_jax.py). The accept
    set must be bit-identical to the oracle, including malformed keys,
    corrupted signatures, S-malleability and non-canonical encodings."""
    cases = []
    for i in range(6):
        seed, pk = _keypair(100 + i)
        msg = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        sig = ref.sign(seed, msg)
        cases.append((pk, msg, sig))
    seed, pk = _keypair(200)
    msg = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
    sig = ref.sign(seed, msg)
    s2 = int.from_bytes(sig[32:], "little") + ref.L
    cases += [
        (pk, msg, _flip(sig, 1)),             # R corrupted
        (pk, msg, _flip(sig, 45)),            # S corrupted
        (_flip(pk, 7), msg, sig),             # pubkey corrupted
        (pk, bytes(32), sig),                 # wrong message
        (pk, msg, sig[:32] + s2.to_bytes(32, "little")),  # S+L malleable
    ]
    pt = _small_y_point()
    noncanon = int.to_bytes(
        int.from_bytes(ref.compress(pt), "little") + ref.P, 32, "little")
    cases += [(noncanon, bytes(32), bytes(64))]

    # Confirm the device-hash path is what actually runs: the host-hashing
    # packer must NOT be called for all-32-byte batches.
    import unittest.mock as mock

    with mock.patch.object(
            kernel, "precompute_batch",
            side_effect=AssertionError("host hash path used")) as _:
        want = _run(cases)
    assert any(want) and not all(want)


def test_device_and_host_hash_paths_agree():
    pks, msgs, sigs = [], [], []
    for i in range(32):
        seed, pk = _keypair(300 + i)
        m = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        s = ref.sign(seed, m)
        if i % 5 == 4:
            s = _flip(s, i % 64)
        pks.append(pk)
        msgs.append(m)
        sigs.append(s)
    host_arrays, _ = kernel.precompute_batch(pks, msgs, sigs, bucket=32)
    dev_arrays, _ = kernel.precompute_batch_device(pks, msgs, sigs, bucket=32)
    host = np.asarray(kernel.verify_arrays_auto(*host_arrays))
    dev = np.asarray(kernel.verify_arrays_hashed(*dev_arrays))
    assert host.tolist() == dev.tolist()


def test_device_hash_path_rejects_mixed_length_messages():
    # Round-2 advisor finding: messages of mixed length summing to 32*n were
    # silently re-split at 32-byte boundaries and verified against scrambled
    # messages. Each message must be exactly 32 bytes.
    pks, msgs, sigs = [], [], []
    for i in range(2):
        seed, pk = _keypair(400 + i)
        m = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        pks.append(pk)
        msgs.append(m)
        sigs.append(ref.sign(seed, m))
    # 31 + 33 = 64 = 32*2: aggregate length check would pass this.
    msgs = [msgs[0][:31], msgs[1] + b"\x00"]
    with pytest.raises(ValueError, match="32-byte"):
        kernel.precompute_batch_device(pks, msgs, sigs, bucket=32)


def _fake_tpu_pallas(monkeypatch, fake):
    from corda_tpu.ops import ed25519_pallas

    kernel.reset_pallas_state()
    kernel._PALLAS_STATE["available"] = True  # pretend a TPU is present
    monkeypatch.setattr(ed25519_pallas, "verify_arrays_pallas", fake)

    def no_xla(*a):
        raise AssertionError("a Pallas failure must never reach XLA")

    monkeypatch.setattr(kernel, "verify_arrays", no_xla)
    return np.zeros((8, 1024), np.uint32)


def test_pallas_failure_raises_and_is_recorded(monkeypatch):
    # On a TPU a failing kernel must surface, not be answered by the 30x
    # slower XLA graph: the caller's degrade path decides and counts it.
    def fail(a, r, s, h, live):
        raise RuntimeError("mosaic regression")

    arr = _fake_tpu_pallas(monkeypatch, fail)
    try:
        with pytest.raises(RuntimeError, match="mosaic regression"):
            kernel.verify_arrays_auto(arr, arr, arr, arr)
        assert kernel.pallas_failures_total() == 1
        assert kernel.last_backend() is None  # nothing was served
    finally:
        kernel.reset_pallas_state()


def test_pallas_failure_does_not_demote_the_next_call(monkeypatch):
    calls = {"pallas": 0}

    def flaky(a, r, s, h, live):
        calls["pallas"] += 1
        if calls["pallas"] == 1:
            raise RuntimeError("transient allocator hiccup")
        return "pallas-result"

    arr = _fake_tpu_pallas(monkeypatch, flaky)
    try:
        with pytest.raises(RuntimeError):
            kernel.verify_arrays_auto(arr, arr, arr, arr)
        assert kernel.verify_arrays_auto(arr, arr, arr, arr) == "pallas-result"
        assert kernel.last_backend() == "pallas"
        assert kernel.pallas_failures_total() == 1
    finally:
        kernel.reset_pallas_state()


def test_native_pack_parity():
    """The native packer (_cverify.c pack_words) must produce byte-for-byte
    the same word arrays as the numpy path, and reject the same inputs —
    the same authority/fast-path contract as the codec core."""
    import numpy as np
    import pytest

    from corda_tpu.crypto import ref_ed25519 as ref
    from corda_tpu.ops import ed25519_jax

    native = ed25519_jax._cpack_module()
    if native is None:
        pytest.skip("no native toolchain/libcrypto")

    pks, msgs, sigs = [], [], []
    for i in range(37):  # odd size: padding lanes exercised
        seed = bytes([(i % 255) + 1]) * 32
        pks.append(ref.public_key(seed))
        m = (b"pack-%d" % i).ljust(32, b".")
        msgs.append(m)
        sigs.append(ref.sign(seed, m))
    bucket = 64

    raw = native.pack_words(pks, msgs, sigs, bucket)
    got = [np.frombuffer(r, "<u4").reshape(8, bucket) for r in raw]

    m_cat = b"".join(msgs)
    _, _, pk, r_enc, s_raw = ed25519_jax._pack_pk_rs(pks, sigs, 37, bucket)
    m_raw = np.zeros((bucket, 32), np.uint8)
    m_raw[:37] = np.frombuffer(m_cat, np.uint8).reshape(37, 32)
    want = [ed25519_jax._words_of(x) for x in (pk, r_enc, s_raw, m_raw)]
    for g, w, name in zip(got, want, "ARSM"):
        assert np.array_equal(g, w), f"{name} words diverged"

    # Rejection parity: ValueError on a short message / short key / bad sig
    with pytest.raises(ValueError):
        native.pack_words(pks, [b"short"] + msgs[1:], sigs, bucket)
    with pytest.raises(ValueError):
        native.pack_words([b"\x00" * 31] + pks[1:], msgs, sigs, bucket)
    with pytest.raises(ValueError):
        native.pack_words(pks, msgs, [b"\x00" * 63] + sigs[1:], bucket)
    with pytest.raises(ValueError):
        native.pack_words(pks[:-1], msgs, sigs, bucket)  # length mismatch
    with pytest.raises(ValueError):
        native.pack_words(pks, msgs, sigs, 16)  # bucket < n


def test_numpy_fallback_packer_rejects_per_item_like_native(monkeypatch):
    """The numpy fallback of precompute_batch_device must reject malformed
    inputs per-ITEM with the native packer's exact messages and order
    (pk -> msg -> sig), so a host without the native core fails identically
    instead of silently packing garbage lanes."""
    monkeypatch.setattr(kernel, "_CPACK_CACHE", [None])  # force numpy path

    pks, msgs, sigs = [], [], []
    for i in range(4):
        seed, pk = _keypair(500 + i)
        m = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        pks.append(pk)
        msgs.append(m)
        sigs.append(ref.sign(seed, m))

    with pytest.raises(ValueError, match="equal length"):
        kernel.precompute_batch_device(pks[:-1], msgs, sigs, bucket=8)
    with pytest.raises(ValueError, match="bucket smaller than batch"):
        kernel.precompute_batch_device(pks, msgs, sigs, bucket=2)
    with pytest.raises(ValueError, match="pubkeys must be 32 bytes"):
        kernel.precompute_batch_device(
            [b"\x00" * 31] + pks[1:], msgs, sigs, bucket=8)
    with pytest.raises(ValueError, match="32-byte messages"):
        kernel.precompute_batch_device(
            pks, [b"short"] + msgs[1:], sigs, bucket=8)
    with pytest.raises(ValueError, match="sigs must be 64 bytes"):
        kernel.precompute_batch_device(
            pks, msgs, [b"\x00" * 63] + sigs[1:], bucket=8)
    # An item bad in several ways reports its FIRST failure (native order):
    # the pk check fires before the msg check on the same index.
    with pytest.raises(ValueError, match="pubkeys must be 32 bytes"):
        kernel.precompute_batch_device(
            [b"\x00" * 31] + pks[1:], [b"short"] + msgs[1:], sigs, bucket=8)
    # And well-formed input still packs (the happy path stays intact).
    arrays, n = kernel.precompute_batch_device(pks, msgs, sigs, bucket=8)
    assert n == 4 and arrays[0].shape == (8, 8)
