"""The batch-size rule of the Ed25519 device tier, without jax.

A device call is compiled once per bucket, so a batch is padded up to the
next rung of one ladder. The single chip and the mesh pad by this rule
(ops/ed25519_jax.py, ops/sharded.py), and the sidecar keys its stats by
it (crypto/sidecar.py) in processes that never import jax.
"""

from __future__ import annotations

LADDER = (64, 256, 1024, 4096, 16384, 65536)

# Lanes of one Pallas kernel block (ops/ed25519_pallas.LANES_PER_BLOCK):
# a Pallas bucket is a multiple of it.
BLOCK_LANES = 1024


def pick_bucket(n: int, buckets=LADDER) -> int:
    """Static batch-size bucket: jit caches one executable per bucket instead
    of recompiling per request size (p99 protection on the notary path).
    Past the top rung, the next multiple of it. The padding costs the
    Pallas kernel no field work: the dispatch passes the live count, and
    blocks past it are skipped (100,000 lanes run 98 of the 131,072
    bucket's 128 blocks)."""
    for b in buckets:
        if n <= b:
            return b
    return -(-n // buckets[-1]) * buckets[-1]


def pad_to_devices(n: int, n_devices: int) -> int:
    """Smallest multiple of n_devices >= max(n, n_devices): every device of
    a mesh gets an equal slice."""
    return -(-max(n, 1) // n_devices) * n_devices
