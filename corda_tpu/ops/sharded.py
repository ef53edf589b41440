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
from jax.sharding import Mesh, PartitionSpec as P

from . import ed25519_jax
from .buckets import pad_to_devices, pick_bucket

__all__ = ["make_mesh", "sharded_verify_fn", "sharded_verify_hashed_fn",
           "pack", "graphs"]

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

    The batch size must be a multiple of the mesh size (see
    ``ops.buckets.pad_to_devices``; padded lanes simply verify to False).
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


def pack(jobs, mesh: Mesh) -> "ed25519_jax.Packed | None":
    """ed25519_jax.pack on the mesh's bucket rule: the single chip's ladder
    rounded up to a multiple of the mesh size, so every device gets an
    equal slice. The handle records the mesh width."""
    ndev = int(mesh.devices.size)
    packed = ed25519_jax.pack(
        jobs, lambda n: pad_to_devices(pick_bucket(n), ndev))
    return None if packed is None else packed._replace(n_devices=ndev)


def graphs(mesh: Mesh) -> ed25519_jax.Graphs:
    """The mesh's graphs for ed25519_jax.run. They verify every lane (the
    XLA graph), so the live count is ignored."""
    hashed = sharded_verify_hashed_fn(mesh)
    host_hashed = sharded_verify_fn(mesh)
    return ed25519_jax.Graphs(
        lambda a, r, s, m, live: hashed(a, r, s, m),
        lambda a, r, s, h, live: host_hashed(a, r, s, h), pallas=False)
