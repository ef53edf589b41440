"""Batched Ed25519 signature verification as a single JAX/XLA graph.

This is the TPU execution backend for the reference's notary hot loop — the
sequential `for (sig in sigs) EdDSAEngine.verify(...)` at reference:
core/src/main/kotlin/net/corda/core/transactions/SignedTransaction.kt:83-87
(engine built at core/.../crypto/CryptoUtilities.kt:63-96) — re-designed as a
data-parallel kernel: N signatures ride the minor axis of every array and the
whole verification (point decompression, 4-bit-windowed 256-bit double-scalar
multiplication, canonical re-encoding, byte compare) is one jit graph with
static shapes.

Semantics are bit-identical to the conformance oracle
(corda_tpu/crypto/ref_ed25519.py — cofactorless ref10 verify, no S<L range
check, silent y mod p reduction on decompression, encode-compare against the
raw R bytes). Golden-vector tests enforce the match.

Layout: inputs ship to the device as (8, N) uint32 little-endian words
(128 B/signature host->device); limb/window unpacking happens
on device. The verification core (`verify_core`) is shape-polymorphic in the
batch dims so the same math runs under plain XLA here and inside the Pallas
VMEM-resident kernel (corda_tpu/ops/ed25519_pallas.py) on (8, 128) vector
blocks.

The SHA-512 challenge h = H(R || A || M) mod L is computed on the host
(hashlib; messages are short and variable-length — a poor fit for fixed-shape
XLA, and a few microseconds per signature against the millisecond-scale curve
math, which is ~3,800 field multiplies per signature on device).
"""

from __future__ import annotations

import collections
import hashlib
from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from . import enable_persistent_compile_cache
from . import fe25519 as fe
from .buckets import BLOCK_LANES, pick_bucket
from ..obs import trace as _obs

# Importing this module means kernels are coming: share compiled graphs
# across processes (a driver cluster spawns five nodes; each would
# otherwise pay the cold compile).
enable_persistent_compile_cache()
# The verify path's spans (obs.trace.span) land in a running profiler
# session's trace, on the device's clock.
_obs.install_annotation(jax.profiler.TraceAnnotation)
from ..crypto import ref_ed25519 as ref

__all__ = ["verify_batch", "verify_jobs", "verify_stream", "pack",
           "pack_columns", "run", "run_stream", "Packed", "Graphs",
           "precompute_batch", "verify_arrays", "pick_bucket", "verify_core",
           "pallas_failures_total",
           "pallas_blocks_run_total", "pallas_blocks_skipped_total",
           "last_backend", "reset_pallas_state"]

_D = ref.D
_2D = (2 * ref.D) % ref.P
_SQRT_M1 = pow(2, (ref.P - 1) // 4, ref.P)
_L = ref.L


# Field constants are materialised with fe.fill_limbs (scalar fills) rather
# than module-level jnp arrays: Pallas kernels cannot close over array
# constants, and XLA constant-folds the fills to literals anyway.


def _ext_add(p, q):
    """Unified a=-1 twisted-Edwards addition (add-2008-hwcd-3), complete on
    edwards25519 — no exceptional cases, so SIMD lanes never diverge."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = fe.mul(fe.sub(y1, x1), fe.sub(y2, x2))
    b = fe.mul(fe.add(y1, x1), fe.add(y2, x2))
    c = fe.mul(fe.mul(t1, t2), fe.fill_limbs(_2D, t1.shape[1:]))
    d = fe.mul_small(fe.mul(z1, z2), 2)
    e = fe.sub(b, a)
    f = fe.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def _ext_dbl(p):
    """Dedicated doubling (dbl-2008-hwcd, a=-1): 8 field muls, complete."""
    x1, y1, z1, _ = p
    a = fe.sq(x1)
    b = fe.sq(y1)
    c = fe.mul_small(fe.sq(z1), 2)
    # a_coeff=-1: D = -A; G = D + B = B - A; H = D - B = -(A + B)
    e = fe.sub(fe.sub(fe.sq(fe.add(x1, y1)), a), b)
    g = fe.sub(b, a)
    f = fe.sub(g, c)
    h = fe.neg(fe.add(a, b))
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def _masked_sum_entry(table_coords, idx):
    """Per-lane 16-way table lookup as a static mask-sum (no gather; VPU
    elementwise only, so it works identically under XLA and Pallas).

    table_coords: tuple of 4 arrays (16, 20, *batch); idx: (*batch,) int32.
    """
    out = []
    for coord in table_coords:
        acc = coord[0] * (idx == 0).astype(fe.I32)[None]
        for k in range(1, 16):
            acc = acc + coord[k] * (idx == k).astype(fe.I32)[None]
        out.append(acc)
    return tuple(out)


def _build_a_table(neg_a):
    """[0..15]·(-A) as a tuple of 4 stacked (16, 20, *batch) arrays.

    Entries come from the unified add so every one is a valid extended point
    (entry 0 = identity)."""
    x, y, z, t = neg_a
    batch = x.shape[1:]
    zero = fe.fill_limbs(0, batch)
    one = fe.fill_limbs(1, batch)
    entries = [(zero, one, one, zero), neg_a]
    for _ in range(14):
        entries.append(_ext_add(entries[-1], neg_a))
    return tuple(jnp.stack([e[c] for e in entries]) for c in range(4))


# Fixed-base table for B precomputed on host: affine (x, y, t) with z = 1.
def _host_b_table():
    entries = []
    for k in range(16):
        if k == 0:
            entries.append((0, 1, 0))
        else:
            x, y = ref.scalar_mult(k, ref.B)
            entries.append((x, y, x * y % ref.P))
    tab = np.zeros((3, 16, fe.NLIMBS), np.int32)
    for k, (x, y, t) in enumerate(entries):
        tab[0, k] = fe.limbs_of_int(x % ref.P)
        tab[1, k] = fe.limbs_of_int(y % ref.P)
        tab[2, k] = fe.limbs_of_int(t % ref.P)
    return tab


_B_TABLE = _host_b_table()  # (3, 16, 20) int32; z == 1 for every entry


def _b_entry(idx, one, b_table):
    """B-table lookup: static mask-sum, built limb-by-limb from SCALAR table
    entries (scalar * (*batch,) mask broadcasts everywhere, including inside
    Mosaic, which cannot broadcast a (20,) vector along new minor dims).
    b_table indexes like a (3, 16, 20) array — a jnp constant on the XLA
    path, an SMEM ref in the Pallas kernel."""
    masks = [(idx == k).astype(fe.I32) for k in range(16)]
    coords = []
    for c in range(3):
        rows = []
        for limb in range(fe.NLIMBS):
            acc = None
            for k in range(16):
                term = b_table[c, k, limb] * masks[k]
                acc = term if acc is None else acc + term
            rows.append(acc)
        coords.append(jnp.stack(rows))
    return (coords[0], coords[1], one, coords[2])


def _double_scalar_mult_sub(s_nibs, h_nibs, neg_a, b_table,
                            unroll: bool = False):
    """[s]B + [h](-A) via 4-bit windowed Strauss: 64 windows of (4 doublings
    + 2 table adds) — ~2x fewer field multiplies than bit-serial.

    s may be a full 256-bit integer (no range check — oracle semantics).
    s_nibs/h_nibs: (64, *batch) int32 windows, MSB first.
    unroll: trace the 64 windows inline (Pallas) instead of lax.scan (XLA).
    """
    batch = s_nibs.shape[1:]
    a_table = _build_a_table(neg_a)
    one = fe.fill_limbs(1, batch)
    zero = fe.fill_limbs(0, batch)
    acc0 = (zero, one, one, zero)

    def window(acc, s_nib, h_nib):
        for _ in range(4):
            acc = _ext_dbl(acc)
        acc = _ext_add(acc, _b_entry(s_nib, one, b_table))
        acc = _ext_add(acc, _masked_sum_entry(a_table, h_nib))
        return acc

    if unroll:
        acc = acc0
        for t in range(64):
            acc = window(acc, s_nibs[t], h_nibs[t])
        return acc

    def step(acc, nibs):
        return window(acc, nibs[0], nibs[1]), None

    xs = jnp.stack([s_nibs, h_nibs], axis=1)  # (64, 2, *batch)
    acc, _ = jax.lax.scan(step, acc0, xs)
    return acc


# ---------------------------------------------------------------------------
# Device-side unpacking of 32-byte encodings shipped as (8, N) uint32 words.
# Host→device traffic is 8 words per value instead of 256 unpacked int32
# bits / 20 limbs — host packing cost and transfer bytes drop ~18x, and
# the shift/mask unpack fuses into the head of the verify graph.
# ---------------------------------------------------------------------------

def _unpack_limbs(words):
    """(8, *batch) uint32 LE words -> ((20, *batch) int32 limbs of bits
    0..254, (*batch,) int32 sign bit 255).

    Static per-limb loop (Python ints for indices/shifts) — no captured
    index-array constants, so the same code lowers inside Pallas kernels.
    """
    limbs = []
    for i in range(fe.NLIMBS):
        word, shift = (13 * i) // 32, (13 * i) % 32
        lo = words[word] >> jnp.uint32(shift)
        if shift > 19:  # 13 bits spill into the next word
            hi = (words[word + 1] << jnp.uint32(32 - shift)
                  if word + 1 < 8 else jnp.zeros_like(lo))
            lo = lo | hi
        mask = 0xFF if i == fe.NLIMBS - 1 else fe.MASK  # drop bits >= 255
        limbs.append(lo & jnp.uint32(mask))
    sign = (words[7] >> jnp.uint32(31)).astype(jnp.int32)
    return jnp.stack(limbs).astype(fe.I32), sign


def _nibbles_msb(words):
    """(8, *batch) uint32 LE words -> (64, *batch) int32 4-bit windows,
    MSB first. Static per-window loop (Pallas-compatible, as above)."""
    nibs = []
    for j in range(64):
        bit = 255 - 4 * j - 3
        word, shift = bit // 32, bit % 32
        nibs.append((words[word] >> jnp.uint32(shift)) & jnp.uint32(0xF))
    return jnp.stack(nibs).astype(jnp.int32)


def decompress_neg_a(y, a_sign):
    """ref10 ge_frombytes + negate: (point_ok (*batch,), -A extended)."""
    batch = y.shape[1:]
    one = fe.fill_limbs(1, batch)
    yy = fe.sq(y)
    u = fe.sub(yy, one)
    v = fe.add(fe.mul(yy, fe.fill_limbs(_D, batch)), one)
    v3 = fe.mul(fe.sq(v), v)
    v7 = fe.mul(fe.sq(v3), v)
    x = fe.mul(fe.mul(u, v3), fe.pow_p58(fe.mul(u, v7)))
    vxx = fe.mul(v, fe.sq(x))
    ok_direct = fe.eq(vxx, u)
    ok_flip = fe.eq(vxx, fe.neg(u))
    x = fe.select(ok_flip & ~ok_direct,
                  fe.mul(x, fe.fill_limbs(_SQRT_M1, batch)), x)
    point_ok = ok_direct | ok_flip
    parity = fe.freeze(x)[0] & 1
    x = fe.select(parity != a_sign, fe.neg(x), x)
    nx = fe.neg(x)
    return point_ok, (nx, y, one, fe.mul(nx, y))


def encode_compare(rpoint, r_limbs, r_sign, point_ok):
    """Canonical-encode R' and compare against the raw R bytes."""
    rx, ry, rz, _ = rpoint
    zi = fe.inv(rz)
    xr = fe.freeze(fe.mul(rx, zi))
    yr = fe.freeze(fe.mul(ry, zi))
    enc_ok = jnp.all(yr == r_limbs, axis=0) & ((xr[0] & 1) == r_sign)
    return point_ok & enc_ok


def verify_core(y, a_sign, r_limbs, r_sign, s_nibs, h_nibs,
                b_table=None, unroll: bool = False):
    """The verification math on unpacked values; shape-polymorphic in the
    batch dims (XLA path: batch = (N,); Pallas path: batch = (8, 128)).

    y/(r_limbs): (20, *batch) canonical limbs; signs (*batch,);
    nibs (64, *batch); b_table (3, 16, 20) (defaults to the module constant —
    Pallas passes it as a kernel input). Returns bool (*batch,).
    """
    if b_table is None:
        b_table = jnp.asarray(_B_TABLE)
    point_ok, neg_a = decompress_neg_a(y, a_sign)
    rpoint = _double_scalar_mult_sub(s_nibs, h_nibs, neg_a, b_table, unroll)
    return encode_compare(rpoint, r_limbs, r_sign, point_ok)


@jax.jit
def verify_arrays(a_words, r_words, s_words, h_words):
    """The whole-batch verification graph (plain XLA path).

    Args (all (8, N) uint32, little-endian words, batch minor):
      a_words: the 32-byte A (public key) encodings
      r_words: the 32-byte R encodings — raw, NOT reduced
      s_words: the S scalars (no range check — oracle semantics)
      h_words: SHA-512(R||A||M) mod L, computed on host
    Returns bool (N,): accept/reject per signature.
    """
    y, a_sign = _unpack_limbs(a_words)
    r_limbs, r_sign = _unpack_limbs(r_words)
    return verify_core(y, a_sign, r_limbs, r_sign,
                       _nibbles_msb(s_words), _nibbles_msb(h_words))


def _words_of(enc: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 little-endian encodings -> (8, B) uint32 words."""
    return np.ascontiguousarray(enc).view("<u4").T.copy()


def precompute_batch(pubkeys, msgs, sigs, bucket: int | None = None):
    """Host-side packing: 32-byte keys + messages + 64-byte sigs -> four
    (8, bucket) uint32 word arrays (A, R, S, h) for verify_arrays.

    Computes h = SHA-512(R_enc || A_enc || M) mod L with the ORIGINAL encodings
    (ref10: the pk bytes go straight into the hash) and pads to the bucket
    size. All bit/limb unpacking happens on device.
    """
    n = len(sigs)
    b = bucket or pick_bucket(n)
    pk_cat, sig_cat, pk, r_enc, s_raw = _pack_pk_rs(pubkeys, sigs, n, b)
    h_raw = np.zeros((b, 32), np.uint8)
    # Per-signature SHA-512 + big-int mod L: both are C-speed (hashlib and
    # CPython long division); a fully vectorized numpy mod-L was measured
    # SLOWER at 64k-signature batches, so the simple loop stays.
    sha512 = hashlib.sha512
    h_rows = h_raw[:n]
    for i in range(n):
        digest = sha512(sig_cat[64 * i:64 * i + 32]
                        + pk_cat[32 * i:32 * i + 32]
                        + bytes(msgs[i])).digest()
        h = int.from_bytes(digest, "little") % _L
        h_rows[i] = np.frombuffer(h.to_bytes(32, "little"), np.uint8)
    return (_words_of(pk), _words_of(r_enc),
            _words_of(s_raw), _words_of(h_raw)), n


def _pack_pk_rs(pubkeys, sigs, n: int, b: int):
    """Shared byte packing: keys + signatures -> padded (b, 32) uint8 arrays
    for A, R, S. Bulk concatenation + one frombuffer per array: ~10x faster
    than per-row numpy assignment at notary batch sizes."""
    pk_cat = b"".join(bytes(k) for k in pubkeys)
    sig_cat = b"".join(bytes(s) for s in sigs)
    pk = np.zeros((b, 32), np.uint8)
    r_enc = np.zeros((b, 32), np.uint8)
    s_raw = np.zeros((b, 32), np.uint8)
    pk[:n] = np.frombuffer(pk_cat, np.uint8).reshape(n, 32)
    sg = np.frombuffer(sig_cat, np.uint8).reshape(n, 64)
    r_enc[:n] = sg[:, :32]
    s_raw[:n] = sg[:, 32:]
    return pk_cat, sig_cat, pk, r_enc, s_raw


_PALLAS_STATE = {
    "available": None,        # None = unprobed; platform capability only
    "failures_total": 0,
    "last_backend": None,     # "pallas" | "xla": backend of the newest call
    "blocks_run_total": 0,    # kernel blocks that held a live lane
    "blocks_skipped_total": 0,  # padding-only blocks the kernel skipped
}


def _pallas_available() -> bool:
    """The Mosaic kernel runs on a TPU backend (CPU runs the XLA graph)."""
    if _PALLAS_STATE["available"] is None:
        _PALLAS_STATE["available"] = jax.devices()[0].platform == "tpu"
    return _PALLAS_STATE["available"]


def last_backend() -> str | None:
    """Which backend ("pallas"/"xla") the most recent verify_arrays_auto
    call actually dispatched to."""
    return _PALLAS_STATE["last_backend"]


def pallas_failures_total() -> int:
    """Pallas calls that raised in this process (each one re-raised)."""
    return _PALLAS_STATE["failures_total"]


def pallas_blocks_run_total() -> int:
    """1024-lane kernel blocks run in this process: those with a live lane."""
    return _PALLAS_STATE["blocks_run_total"]


def pallas_blocks_skipped_total() -> int:
    """Padding-only kernel blocks skipped in this process."""
    return _PALLAS_STATE["blocks_skipped_total"]


def reset_pallas_state() -> None:
    """Forget the platform probe, failure history and block counts (tests)."""
    _PALLAS_STATE.update(available=None, failures_total=0, last_backend=None,
                         blocks_run_total=0, blocks_skipped_total=0)


def _uses_pallas(n: int) -> bool:
    """Whether a batch of `n` lanes runs the Pallas kernel (1024-lane
    blocks) rather than the XLA graph."""
    return _pallas_available() and n % BLOCK_LANES == 0


def _live_blocks(live: int) -> int:
    """Kernel blocks holding one of the first `live` lanes: those it runs."""
    return -(-live // BLOCK_LANES)


def verify_arrays_auto(a_words, r_words, s_words, h_words, live=None):
    """Best backend for the word-array contract: the VMEM-resident Pallas
    kernel on TPU (batch a multiple of 1024), the plain XLA graph
    otherwise.

    `live` (None for all) says the first `live` lanes carry signatures and
    the rest are padding: the kernel skips the blocks past them, which
    read reject. The XLA graph ignores it and verifies every lane.

    A Pallas failure RAISES (after counting it in pallas_failures_total()):
    answering from the XLA graph instead would hide a broken kernel behind
    a 30x slower one. The node's per-batch
    degrade path (statemachine._degrade_and_reverify) owns what a failed
    batch does next, and counts it.
    """
    n = a_words.shape[1]
    if _uses_pallas(n):
        from . import ed25519_pallas

        live = n if live is None else int(live)
        try:
            # An int32 operand, never static: one executable per bucket.
            out = ed25519_pallas.verify_arrays_pallas(
                a_words, r_words, s_words, h_words, np.int32(live))
        except Exception:
            _PALLAS_STATE["failures_total"] += 1
            raise
        blocks = _live_blocks(live)
        _PALLAS_STATE["last_backend"] = "pallas"
        _PALLAS_STATE["blocks_run_total"] += blocks
        _PALLAS_STATE["blocks_skipped_total"] += n // BLOCK_LANES - blocks
        return out
    _PALLAS_STATE["last_backend"] = "xla"
    return verify_arrays(a_words, r_words, s_words, h_words)


def precompute_batch_device(pubkeys, msgs, sigs, bucket: int | None = None):
    """Host packing for the fully-on-device path: NO host hashing. All
    messages must be exactly 32 bytes (the notary workload: tx ids). Returns
    ((a_words, r_words, s_words, m_words), n) for verify_arrays_hashed —
    the per-signature SHA-512 + mod-L loop of precompute_batch becomes a
    batched device graph (ops/sha512_jax.py).

    Packing runs in the native core when available (`_cverify.c
    pack_words`, GIL released): the numpy path's per-item bytes() +
    join + transpose-copy was the measured bottleneck of the depth-2
    streaming pipeline (host pack rate < kernel rate starved the device).
    Identical semantics either way — byte-for-byte equal word arrays,
    same ValueError on non-32-byte messages (parity suite:
    tests/test_ed25519_jax.py::test_native_pack_parity)."""
    n = len(sigs)
    b = bucket or pick_bucket(n)
    native = _cpack_module()
    if native is not None:
        raw_a, raw_r, raw_s, raw_m = native.pack_words(
            pubkeys, msgs, sigs, b)

        def words(raw: bytes) -> np.ndarray:
            return np.frombuffer(raw, "<u4").reshape(8, b)

        return (words(raw_a), words(raw_r), words(raw_s), words(raw_m)), n
    # Per-ITEM checks, not aggregate: mixed lengths summing to the right
    # total would silently re-split at fixed boundaries and verify against
    # scrambled lanes (round-2 advisor finding). Same order and messages
    # as the native packer's want_len loop (pk -> msg -> sig per item) so
    # either path rejects malformed input identically.
    raw = [bytes(m) for m in msgs]
    if len(raw) != n or len(pubkeys) != n:
        raise ValueError("pubkeys, msgs and sigs must have equal length")
    if b < n:
        raise ValueError("bucket smaller than batch")
    for pk, m, s in zip(pubkeys, raw, sigs):
        if len(bytes(pk)) != 32:
            raise ValueError("pubkeys must be 32 bytes")
        if len(m) != 32:
            raise ValueError("device-hash path requires 32-byte messages")
        if len(bytes(s)) != 64:
            raise ValueError("sigs must be 64 bytes")
    m_cat = b"".join(raw)
    _, _, pk, r_enc, s_raw = _pack_pk_rs(pubkeys, sigs, n, b)
    m_raw = np.zeros((b, 32), np.uint8)
    m_raw[:n] = np.frombuffer(m_cat, np.uint8).reshape(n, 32)
    return (_words_of(pk), _words_of(r_enc),
            _words_of(s_raw), _words_of(m_raw)), n


_CPACK_CACHE: list = []


def _cpack_module():
    """The native packer, or None (no toolchain / no libcrypto): the numpy
    path below is the behavioural authority and permanent fallback."""
    if not _CPACK_CACHE:
        try:
            from ..native import load_cverify

            mod = load_cverify()
            _CPACK_CACHE.append(
                mod if mod is not None and hasattr(mod, "pack_words")
                else None)
        except Exception:
            _CPACK_CACHE.append(None)
    return _CPACK_CACHE[0]


def verify_arrays_hashed(a_words, r_words, s_words, m_words, live=None):
    """End-to-end device verification for 32-byte messages: the challenge
    h = SHA-512(R||A||M) mod L is computed on device, then fed to the best
    available verify backend (Pallas on TPU, XLA otherwise) with `live`."""
    from . import sha512_jax

    h_words = sha512_jax.challenge_words(r_words, a_words, m_words)
    return verify_arrays_auto(a_words, r_words, s_words, h_words, live)


# The device tier's two halves: pack() turns a batch into one handle on the
# host; run() dispatches it and places the verdicts. The single chip and the
# mesh (ops/sharded.py) differ only in the bucket rule and the graphs.


def _device_bucket(n_good: int) -> int:
    """The single chip's bucket for a call of `n_good` well-formed lanes."""
    bucket = pick_bucket(n_good)
    if _pallas_available():
        bucket = max(bucket, BLOCK_LANES)
    return bucket


class Packed(NamedTuple):
    """One batch packed for a device call, well-formed lanes first."""

    mask: np.ndarray      # bool (n,): the caller's well-formed lanes
    n_good: int           # their count: lanes 0..n_good-1 of the arrays
    bucket: int           # lanes dispatched
    arrays: tuple         # four (8, bucket) uint32 word arrays
    hashed: bool          # the device hashes the challenge (32-byte msgs)
    n_devices: int = 1    # devices the bucket is split over


class Graphs(NamedTuple):
    """A tier's verify graphs, each called as graph(*arrays, live=n_good)."""

    hashed: Callable       # (A, R, S, M) words: challenge hashed on device
    host_hashed: Callable  # (A, R, S, h) words: challenge from the host
    pallas: bool           # may run the Pallas kernel (the `blocks` stat)


SINGLE_CHIP = Graphs(verify_arrays_hashed, verify_arrays_auto, pallas=True)


def pack(jobs, bucket_of) -> Packed | None:
    """The host half for job objects (`scheme`, `pubkey`, `message`, `sig`
    attributes; every scheme "ed25519"): None when no lane is well-formed.

    The native `pack_jobs` reads the job list once, straight into the
    word arrays of the well-formed lanes and their mask, so no per-job
    Python list is built. Where it declines (a message that is not 32
    bytes takes the host-hashed graph; a field that is not plain bytes; no
    native core), the jobs become columns for pack_columns."""
    pack_jobs = getattr(_cpack_module(), "pack_jobs", None)
    if pack_jobs is not None:
        with _obs.span("verify.pack"):
            native = pack_jobs(jobs, bucket_of)
        if native is not None:
            mask, n_good, bucket, raw = native
            if not n_good:
                return None
            return Packed(np.frombuffer(mask, bool), n_good, bucket,
                          tuple(np.frombuffer(r, "<u4").reshape(8, bucket)
                                for r in raw), True)
    with _obs.span("verify.prepare"):
        columns = ([j.pubkey for j in jobs], [j.message for j in jobs],
                   [j.sig for j in jobs])
    return pack_columns(*columns, bucket_of)


def pack_columns(pubkeys, msgs, sigs, bucket_of) -> Packed | None:
    """The host half for key, message and signature columns: None when no
    lane is well-formed (a 32-byte key and a 64-byte signature). All
    32-byte messages (tx ids) hash on the device; otherwise the host
    hashes (precompute_batch)."""
    n = len(sigs)
    with _obs.span("verify.prepare"):
        mask = np.fromiter((len(bytes(pubkeys[i])) == 32
                            and len(bytes(sigs[i])) == 64
                            for i in range(n)), bool, n)
        n_good = int(np.count_nonzero(mask))
        if not n_good:
            return None
        if n_good < n:
            good = np.flatnonzero(mask).tolist()
            pubkeys, msgs, sigs = ([col[i] for i in good]
                                   for col in (pubkeys, msgs, sigs))
        bucket = bucket_of(n_good)
        hashed = all(len(bytes(m)) == 32 for m in msgs)
    with _obs.span("verify.pack"):
        packer = precompute_batch_device if hashed else precompute_batch
        arrays, _ = packer(pubkeys, msgs, sigs, bucket=bucket)
    return Packed(mask, n_good, bucket, arrays, hashed)


def run_stream(items, graphs: Graphs, depth: int = 0):
    """The device half: yields the verdicts, bool (lanes,), of each
    (packed, lanes) item in order; a None handle answers all-reject
    without a call.

    Each handle's call is enqueued with its live count (the kernel skips
    the blocks past it), read back, and its verdicts placed by the mask.
    JAX dispatch is asynchronous, so up to ``depth`` calls stay in flight
    while the next item is drawn: the host packs batch k+1 while the
    device runs batch k."""
    source = iter(items)
    inflight = collections.deque()  # (packed, lanes, device_out)
    while True:
        item = next(source, None) if len(inflight) <= depth else None
        if item is not None:
            packed, lanes = item
            out = None
            if packed is not None:
                stats = {"lanes": packed.n_good, "bucket": packed.bucket}
                if graphs.pallas and _uses_pallas(packed.bucket):
                    stats["blocks"] = _live_blocks(packed.n_good)
                graph = graphs.hashed if packed.hashed else graphs.host_hashed
                with _obs.span("verify.dispatch", **stats):
                    out = graph(*packed.arrays, live=packed.n_good)
            inflight.append((packed, lanes, out))
            continue
        if not inflight:
            return
        packed, lanes, out = inflight.popleft()
        if packed is None:
            yield np.zeros(lanes, bool)
            continue
        with _obs.span("verify.readback"):
            out = np.asarray(out)
        with _obs.span("verify.scatter"):
            n_good = packed.n_good
            if n_good == len(packed.mask):
                ok = out[:n_good].copy()
            else:
                ok = np.zeros(len(packed.mask), bool)
                ok[packed.mask] = out[:n_good]
        yield ok


def run(packed: Packed | None, graphs: Graphs, lanes: int) -> np.ndarray:
    """run_stream for one call: the verdicts of the caller's `lanes`."""
    return next(run_stream(((packed, lanes),), graphs))


def verify_jobs(jobs) -> np.ndarray:
    """Batched verify of job objects on this chip: bool (len(jobs),).
    Malformed lanes reject."""
    return run(pack(jobs, _device_bucket), SINGLE_CHIP, len(jobs))


def verify_batch(pubkeys, msgs, sigs) -> np.ndarray:
    """Batched verify of key, message and signature columns on this chip:
    bool (len(sigs),).

    Malformed inputs (wrong lengths, junk bytes) reject — never raise —
    matching the reference where verify exceptions surface as rejection
    (reference: core/.../transactions/SignedTransaction.kt:83-87).
    """
    return run(pack_columns(pubkeys, msgs, sigs, _device_bucket),
               SINGLE_CHIP, len(sigs))


def verify_stream(batches, bucket: int | None = None, depth: int = 2):
    """Pipelined streaming verify: yields one bool array per input batch,
    in order.

    ``batches`` is an iterable of (pubkeys, msgs, sigs) triples; ``bucket``
    fixes every call's bucket (default: the single chip's rule). Up to
    ``depth`` batches stay in flight while the host packs the next one
    (run_stream); peak device residency is ``depth + 1`` batches.
    """
    bucket_of = _device_bucket if bucket is None else (lambda n: bucket)
    items = ((pack_columns(*b, bucket_of), len(b[2])) for b in batches)
    yield from run_stream(items, SINGLE_CHIP, depth)
