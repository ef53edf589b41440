"""JAX/XLA kernels — the TPU data plane of corda_tpu.

Batched field arithmetic (fe25519), Ed25519 signature verification
(ed25519_jax) and SHA-256 Merkle hashing (sha256_jax) replace the sequential
per-signature JVM loops on the reference's notary hot path (reference:
core/src/main/kotlin/net/corda/core/transactions/SignedTransaction.kt:83-87).
"""

import os as _os
import sys as _sys


def last_backend_if_loaded():
    """Which kernel backend ("pallas" | "xla" | None) served the newest
    ed25519 verify call — read WITHOUT importing the kernel module. Every
    stamping site (RPC node_metrics, bench config stamps) must use this:
    stamping must never be the thing that pulls jax into a host-only
    process (one process owns the chip; a stamp must not claim it)."""
    mod = _sys.modules.get("corda_tpu.ops.ed25519_jax")
    if mod is None:
        return None
    try:
        return mod.last_backend()
    except Exception:
        return None


_CPU_SIG: str | None = None


def host_cpu_signature() -> str:
    """Stable 8-hex signature of THIS host's CPU feature set.

    XLA's persistent cache stores AOT-compiled HOST code for the CPU
    backend: an entry compiled on a machine with (say) AVX-512 and loaded
    on one without it is a latent SIGILL. CPU-backend entries therefore
    live in a per-signature partition of the checkout cache (see
    compile_cache_dir)."""
    global _CPU_SIG
    if _CPU_SIG is None:
        import hashlib
        import platform

        feats = ""
        try:
            with open("/proc/cpuinfo") as f:
                for line in f:
                    # x86 "flags", arm64 "Features"; sorted so kernel
                    # ordering changes don't shift the key.
                    if line.startswith(("flags", "Features")):
                        feats = " ".join(sorted(
                            line.split(":", 1)[1].split()))
                        break
        except OSError:
            pass  # non-procfs platform: machine arch alone partitions
        raw = f"{platform.machine()}|{feats}"
        _CPU_SIG = hashlib.sha256(raw.encode()).hexdigest()[:8]
    return _CPU_SIG


CHECKOUT_ROOT = _os.path.dirname(_os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))


def compile_cache_dir(cpu: bool | None = None) -> str:
    """The ONE persistent compile-cache directory of this process and of
    every child the driver spawns.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set, verbatim. Otherwise the
    cache lives at ``<checkout>/.jax_cache`` (gitignored): a fixed path,
    because the path is part of the cache key, so a directory that moves
    never hits. CPU-backend processes use a ``cpu-<host_cpu_signature()>``
    partition beneath it; ``cpu`` defaults to whether this process is
    pinned to the CPU (``JAX_PLATFORMS=cpu``)."""
    explicit = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if explicit:
        return explicit
    if cpu is None:
        cpu = _os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    root = _os.path.join(CHECKOUT_ROOT, ".jax_cache")
    if cpu:
        return _os.path.join(root, f"cpu-{host_cpu_signature()}")
    return root


def enable_persistent_compile_cache() -> None:
    """Point XLA's persistent compilation cache at compile_cache_dir() so
    the kernels compile once per checkout, not once per process: a cold
    Pallas compile is tens of seconds per bucket, and the driver's cluster
    (sidecar + nodes) plus the bench/smoke children would each pay it.
    Idempotent."""
    import jax

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    want_locations = _os.environ.get(
        "CORDA_TPU_FULL_TRACEBACK_LOCATIONS", "")
    # Caller tracebacks embed in the lowered module's debug locations, and
    # for Pallas kernels those locations reach the serialized Mosaic
    # payload — so the CACHE KEY depended on the call site's line numbers
    # (measured: 37 distinct keys for one identical kernel). Location-free
    # lowering makes the key a function of the kernel alone. Trade-off:
    # XLA error messages lose caller frames — set
    # CORDA_TPU_FULL_TRACEBACK_LOCATIONS=1 when debugging a lowering
    # failure.
    jax.config.update(
        "jax_include_full_tracebacks_in_locations",
        want_locations.strip().lower() not in ("", "0", "false", "no"))
