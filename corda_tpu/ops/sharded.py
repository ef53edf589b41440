"""Multi-chip sharding of the batched Ed25519 verify kernel.

The reference's whitepaper singles out signature verification as the
embarrassingly-parallel hotspot ("signatures can easily be verified in
parallel", reference: docs/source/whitepaper/corda-technical-whitepaper.tex:
1597-1604).  On TPU the natural realisation is SPMD over a device mesh: the
signature batch axis — the minor axis of every kernel array — is sharded
across a 1-D ``jax.sharding.Mesh`` with ``jax.shard_map``, so each chip
decompresses and double-scalar-multiplies its own slice of the batch.  No
collectives are needed on the verify path itself (each lane is an independent
signature); the outputs come back sharded and XLA gathers them only if the
host reads the full array.

The same code runs on a single chip (mesh of 1), an 8-device virtual CPU mesh
(tests / the driver's dry-run), or a real multi-chip slice — the mesh is the
only degree of freedom.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import ed25519_jax, fe25519 as fe
from ..obs import trace as _obs

__all__ = ["make_mesh", "sharded_verify_fn", "sharded_verify_hashed_fn",
           "verify_batch_sharded", "pad_to_devices",
           "pack_batch_sharded", "dispatch_packed", "PackedShardedBatch"]

BATCH_AXIS = "sigs"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices (all if None)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"need {n_devices} devices, have {len(devices)}; "
                    "set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    "JAX_PLATFORMS=cpu for a virtual CPU mesh"
                )
            devices = devices[:n_devices]
    # lint: allow(no-jit-in-hotpath) make_mesh IS the mesh constructor; every caller memoises its result (provider.mesh, bench setup) — it never runs per batch
    return Mesh(np.array(devices), (BATCH_AXIS,))


def pad_to_devices(n: int, n_devices: int) -> int:
    """Smallest multiple of n_devices >= max(n, n_devices)."""
    return -(-max(n, 1) // n_devices) * n_devices


# Kernel array layout: four (8, N) uint32 word arrays, batch minor.
_IN_SPECS = (P(None, BATCH_AXIS),) * 4
_OUT_SPEC = P(BATCH_AXIS)


_FN_CACHE: dict[tuple, object] = {}


def _sharded_fn(graph_fn, mesh: Mesh):
    """shard_map + jit a per-lane verify graph over ``mesh``, cached per
    (graph, mesh). check_vma=False: the scan carry seeds from
    device-invariant curve constants which the VMA checker would otherwise
    force us to pcast; the kernels are per-lane independent so replication
    analysis adds nothing here."""
    key = (graph_fn, mesh)
    fn = _FN_CACHE.get(key)
    if fn is None:
        # Sharded compiles share the checkout's persistent cache
        # (ops.compile_cache_dir) with the single-chip kernels.
        from . import enable_persistent_compile_cache

        enable_persistent_compile_cache()
        # lint: allow(no-jit-in-hotpath) this IS the keyed executable cache the rule routes hot paths through: one shard_map+jit per (graph, mesh), stored in _FN_CACHE above
        inner = jax.shard_map(
            graph_fn, mesh=mesh, in_specs=_IN_SPECS, out_specs=_OUT_SPEC,
            check_vma=False,
        )
        # lint: allow(no-jit-in-hotpath) cache-miss arm of _FN_CACHE: compiled once per key, then every dispatch reuses the stored executable
        fn = _FN_CACHE[key] = jax.jit(inner)
    return fn


def sharded_verify_fn(mesh: Mesh):
    """jit-compiled SPMD verify over ``mesh``: same signature/semantics as
    ``ed25519_jax.verify_arrays`` but with the batch axis sharded.

    The batch size must be a multiple of the mesh size (use
    :func:`pad_to_devices`; padded lanes simply verify to False).
    """
    return _sharded_fn(ed25519_jax.verify_arrays.__wrapped__, mesh)


def _verify_hashed_graph(a_words, r_words, s_words, m_words):
    """Undecorated fully-on-device graph: SHA-512 challenge + mod-L + verify.
    Per-lane independent, so sharding the batch axis needs no collectives —
    each device hashes and verifies its own slice. Reuses the single-chip
    challenge graph (not a copy) so the tiers cannot drift."""
    from . import sha512_jax

    h_words = sha512_jax.challenge_words.__wrapped__(
        r_words, a_words, m_words)
    return ed25519_jax.verify_arrays.__wrapped__(
        a_words, r_words, s_words, h_words)


def sharded_verify_hashed_fn(mesh: Mesh):
    """SPMD twin of ``ed25519_jax.verify_arrays_hashed``: batch axis sharded
    over ``mesh``, challenge hashing included on device (32-byte messages)."""
    return _sharded_fn(_verify_hashed_graph, mesh)


class PackedShardedBatch:
    """Host-packed kernel arrays awaiting a mesh dispatch.

    The pack half (CPU: decompress limbs, radix-split words, pad to the
    bucket) and the dispatch half (device: the sharded verify executable)
    are split so a pipelined caller — the sidecar's depth-2 executor — can
    pack batch N+1 on the host while batch N runs on the mesh."""

    __slots__ = ("n", "good", "arrays", "fn", "bucket", "n_devices")

    def __init__(self, n, good, arrays, fn, bucket, n_devices):
        self.n = n                  # total lanes requested (incl. malformed)
        self.good = good            # indices packed into the arrays
        self.arrays = arrays        # four (8, bucket) uint32 word arrays
        self.fn = fn                # jit(shard_map) executable, mesh-bound
        self.bucket = bucket        # padded lane count actually dispatched
        self.n_devices = n_devices

    @property
    def pad_lanes(self) -> int:
        """Lanes dispatched that carry no real signature (bucket ladder
        round-up + pad_to_devices) — the waste the stats attribute."""
        return self.bucket - len(self.good)


def pack_batch_sharded(pubkeys, msgs, sigs,
                       mesh: Mesh) -> "PackedShardedBatch | None":
    """Host half of the sharded verify: filter malformed lanes, pick the
    bucket (rounded to a multiple of the mesh size so every device gets an
    equal slice), and columnar-pack the kernel arrays. Returns None when no
    lane is well-formed (the caller answers all-False without a dispatch).

    The returned executable is the cached jit(shard_map) for this mesh —
    in/out shardings are fixed by _IN_SPECS/_OUT_SPEC, so repeated
    dispatches at the same bucket reuse one executable and never
    re-partition."""
    n = len(sigs)
    with _obs.span("verify.prepare"):
        good = [i for i in range(n)
                if len(bytes(pubkeys[i])) == 32 and len(bytes(sigs[i])) == 64]
        if not good:
            return None
        ndev = mesh.devices.size
        bucket = pad_to_devices(ed25519_jax.pick_bucket(len(good)), ndev)
        gp = [pubkeys[i] for i in good]
        gm = [msgs[i] for i in good]
        gs = [sigs[i] for i in good]
        hashed = ed25519_jax.device_hash_eligible(gm)
    with _obs.span("verify.pack"):
        if hashed:
            arrays, _ = ed25519_jax.precompute_batch_device(gp, gm, gs,
                                                            bucket=bucket)
            fn = sharded_verify_hashed_fn(mesh)
        else:
            arrays, _ = ed25519_jax.precompute_batch(gp, gm, gs,
                                                     bucket=bucket)
            fn = sharded_verify_fn(mesh)
    return PackedShardedBatch(n, good, arrays, fn, bucket, ndev)


def dispatch_packed(packed: PackedShardedBatch) -> np.ndarray:
    """Device half: run the mesh executable and scatter lane results back
    to the caller's index space (padded lanes verify False and are never
    visible — bool[packed.n] covers exactly the requested lanes)."""
    with _obs.span("verify.dispatch", lanes=len(packed.good),
                   bucket=packed.bucket):
        pending = packed.fn(*packed.arrays)
    with _obs.span("verify.readback"):
        out = np.asarray(pending)
    with _obs.span("verify.scatter"):
        ok = np.zeros(packed.n, bool)
        for j, i in enumerate(packed.good):
            ok[i] = out[j]
    return ok


def verify_batch_sharded(pubkeys, msgs, sigs, mesh: Mesh) -> np.ndarray:
    """End-to-end sharded verify: bool[len(sigs)], malformed inputs reject.

    Host packing and path dispatch are shared with the single-chip tier:
    all-32-byte messages (tx ids) hash on device; the bucket is rounded up to
    a multiple of the mesh size so every device gets an equal slice.
    """
    packed = pack_batch_sharded(pubkeys, msgs, sigs, mesh)
    if packed is None:
        return np.zeros(len(sigs), bool)
    return dispatch_packed(packed)
