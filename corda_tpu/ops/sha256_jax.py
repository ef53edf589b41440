"""Batched SHA-256 as a JAX kernel — the second notary hot spot.

The reference computes every transaction id as a Merkle root over
per-component SHA-256 hashes, sequentially on the JVM (reference:
core/src/main/kotlin/net/corda/core/transactions/WireTransaction.kt:45-52,
core/.../transactions/MerkleTransaction.kt:26-38,62-99).  At notary batch
sizes that is thousands of small hashes per micro-batch; on TPU they all ride
one fixed-shape graph: the 64-round compression runs in a ``lax.scan`` with
the batch axis minor, so N messages hash in lock-step on the VPU lanes.

Layout mirrors fe25519: words are uint32, arrays are word-major / batch-minor
(``(16, N)`` words per block), all shapes static.  Messages of equal padded
block count share one executable; the host packer buckets by block count.

Byte-identical to hashlib.sha256 — golden-vector tests enforce it.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "sha256_blocks", "pack_messages", "sha256_fixed", "sha256_many",
    "sha256_pair_words", "merkle_root_device", "merkle_roots_device",
    "hash_many_auto",
]

# Below this many messages one hashlib loop beats the kernel end-to-end:
# the device win is batch-parallelism, and host packing + transfer overhead
# amortises only at scale. The default dates from a chip reached over a
# slow link (kernel-resident crossed hashlib at ~64k hashes); it has not
# been re-measured on a directly attached chip. Override with
# CORDA_TPU_SHA256_DEVICE_MIN.
DEVICE_MIN_HASHES_DEFAULT = 65536

U32 = jnp.uint32

_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2], np.uint32)

_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19], np.uint32)


def _rotr(x, n):
    return (x >> U32(n)) | (x << U32(32 - n))


def _compress(state, block):
    """One compression: state (8, N) uint32, block (16, N) uint32."""

    def round_step(carry, k):
        (a, b, c, d, e, f, g, h), win = carry
        w = win[0]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k + w
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        # W[t+16] = s1(W[t+14]) + W[t+9] + s0(W[t+1]) + W[t]
        ls0 = _rotr(win[1], 7) ^ _rotr(win[1], 18) ^ (win[1] >> U32(3))
        ls1 = _rotr(win[14], 17) ^ _rotr(win[14], 19) ^ (win[14] >> U32(10))
        neww = ls1 + win[9] + ls0 + win[0]
        win = jnp.concatenate([win[1:], neww[None]])
        return ((t1 + t2, a, b, c, d + t1, e, f, g), win), None

    init = (tuple(state[i] for i in range(8)), block)
    (vars_, _), _ = jax.lax.scan(round_step, init, jnp.asarray(_K, U32))
    return state + jnp.stack(vars_)


@partial(jax.jit, static_argnames=())
def sha256_blocks(blocks):
    """Full hash over pre-padded blocks: (nblocks, 16, N) uint32 -> (8, N).

    The block axis is scanned (sequential chaining is inherent to SHA-256);
    all batch-wise parallelism is in the minor axis.
    """
    n = blocks.shape[-1]
    state0 = jnp.broadcast_to(jnp.asarray(_H0, U32)[:, None], (8, n))

    def step(state, block):
        return _compress(state, block), None

    state, _ = jax.lax.scan(step, state0, blocks)
    return state


def pack_messages(msgs: np.ndarray) -> np.ndarray:
    """Pad equal-length messages: (N, L) uint8 -> (nblocks, 16, N) uint32.

    Standard SHA-256 padding (0x80, zeros, 64-bit big-endian bit length).
    """
    msgs = np.ascontiguousarray(msgs, np.uint8)
    n, length = msgs.shape
    nblocks = (length + 8) // 64 + 1
    padded = np.zeros((n, nblocks * 64), np.uint8)
    padded[:, :length] = msgs
    padded[:, length] = 0x80
    padded[:, -8:] = np.frombuffer(
        (length * 8).to_bytes(8, "big"), np.uint8)
    words = padded.reshape(n, nblocks, 16, 4)
    words = (words[..., 0].astype(np.uint32) << 24
             | words[..., 1].astype(np.uint32) << 16
             | words[..., 2].astype(np.uint32) << 8
             | words[..., 3].astype(np.uint32))
    return np.transpose(words, (1, 2, 0)).copy()  # (nblocks, 16, N)


def _digest_bytes(state) -> np.ndarray:
    """(8, N) uint32 device state -> (N, 32) uint8 big-endian digests."""
    st = np.asarray(state).T  # (N, 8)
    return np.ascontiguousarray(st.astype(">u4")).view(np.uint8).reshape(-1, 32)


def sha256_fixed(msgs: np.ndarray) -> np.ndarray:
    """Batched digest of equal-length messages: (N, L) uint8 -> (N, 32) uint8."""
    return _digest_bytes(sha256_blocks(jnp.asarray(pack_messages(msgs), U32)))


def sha256_many(msgs: list[bytes]) -> list[bytes]:
    """Digest a ragged batch, bucketed by padded block count.

    Messages sharing a block count run as one kernel call (their individual
    length padding is applied on the host, so in-bucket lengths may differ).
    """
    out: list[bytes | None] = [None] * len(msgs)
    buckets: dict[int, list[int]] = {}
    for i, m in enumerate(msgs):
        buckets.setdefault((len(m) + 8) // 64 + 1, []).append(i)
    for nblocks, idxs in buckets.items():
        packed = np.zeros((len(idxs), nblocks, 16), np.uint32)
        for j, i in enumerate(idxs):
            m = msgs[i]
            sub = pack_messages(np.frombuffer(m, np.uint8)[None])
            packed[j] = sub[:, :, 0]
        blocks = jnp.asarray(np.transpose(packed, (1, 2, 0)), U32)
        digests = _digest_bytes(sha256_blocks(blocks))
        for j, i in enumerate(idxs):
            out[i] = digests[j].tobytes()
    return out  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# Merkle tree reduction on device
# ---------------------------------------------------------------------------

# A 64-byte message is exactly one data block plus one constant padding block.
_PAD_BLOCK_64 = pack_messages(np.zeros((1, 64), np.uint8))[1, :, 0]  # (16,)


@jax.jit
def sha256_pair_words(left, right):
    """Merkle node hash sha256(l || r) fully in words.

    left/right: (8, N) uint32 digests -> (8, N) uint32 digest.
    """
    n = left.shape[-1]
    block1 = jnp.concatenate([left, right])  # (16, N)
    state = _compress(jnp.broadcast_to(jnp.asarray(_H0, U32)[:, None], (8, n)),
                      block1)
    pad = jnp.broadcast_to(jnp.asarray(_PAD_BLOCK_64, U32)[:, None], (16, n))
    return _compress(state, pad)


def hash_many_auto(msgs: list[bytes],
                   device_min: int | None = None) -> tuple[list[bytes], str]:
    """(digests, backend): hashlib below the crossover batch size, the
    batched device kernel at or above it. The ONE dispatch predicate for
    framework bulk hashing (the resolve path's tx-id recomputation). A
    device failure raises: the host path is routing by size, never a
    stand-in for a device that failed."""
    import hashlib
    import os

    if device_min is None:
        device_min = int(os.environ.get("CORDA_TPU_SHA256_DEVICE_MIN",
                                        DEVICE_MIN_HASHES_DEFAULT))
    if len(msgs) >= device_min:
        return sha256_many(msgs), "device"
    return [hashlib.sha256(m).digest() for m in msgs], "host"


def merkle_roots_device(leaf_digest_groups: list[list[bytes]]) -> list[bytes]:
    """Many Merkle roots (odd-duplicate rule) in batched device calls.

    Trees are bucketed by leaf count; every same-count tree reduces
    level-by-level TOGETHER (one sha256_pair_words call hashes the level's
    nodes of every tree in the bucket). The per-tree semantics match
    crypto.merkle.MerkleTree.build bit-for-bit.
    """
    out: list[bytes | None] = [None] * len(leaf_digest_groups)
    buckets: dict[int, list[int]] = {}
    for i, leaves in enumerate(leaf_digest_groups):
        if not leaves:
            raise ValueError("Cannot calculate Merkle root on empty hash list.")
        buckets.setdefault(len(leaves), []).append(i)
    for n_leaves, idxs in buckets.items():
        m = len(idxs)
        flat = b"".join(b"".join(leaf_digest_groups[i]) for i in idxs)
        arr = np.frombuffer(flat, np.uint8).reshape(m * n_leaves, 32)
        words = np.ascontiguousarray(arr).view(">u4").astype(np.uint32)
        level = jnp.asarray(words.reshape(m, n_leaves, 8).transpose(2, 0, 1),
                            U32)  # (8, m, L)
        width = n_leaves
        while width > 1:
            if width % 2:
                level = jnp.concatenate([level, level[:, :, -1:]], axis=2)
                width += 1
            left = level[:, :, 0::2].reshape(8, -1)
            right = level[:, :, 1::2].reshape(8, -1)
            level = sha256_pair_words(left, right).reshape(8, m, width // 2)
            width //= 2
        digests = _digest_bytes(level.reshape(8, m))
        for j, i in enumerate(idxs):
            out[i] = digests[j].tobytes()
    return out  # type: ignore[return-value]


def merkle_root_device(leaf_hashes: list[bytes]) -> bytes:
    """Merkle root with the reference's odd-node-duplicate rule, reduced
    level-by-level on device (MerkleTransaction.kt:62-99 semantics — matches
    corda_tpu.crypto.merkle.MerkleTree.build bit-for-bit).
    """
    if not leaf_hashes:
        raise ValueError("Cannot calculate Merkle root on empty hash list.")
    arr = np.frombuffer(b"".join(leaf_hashes), np.uint8).reshape(-1, 32)
    words = np.ascontiguousarray(arr).view(">u4").astype(np.uint32).T  # (8, N)
    level = jnp.asarray(words, U32)
    while level.shape[1] > 1:
        if level.shape[1] % 2:
            level = jnp.concatenate([level, level[:, -1:]], axis=1)
        level = sha256_pair_words(level[:, 0::2], level[:, 1::2])
    return _digest_bytes(level)[0].tobytes()
