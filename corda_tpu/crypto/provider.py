"""Pluggable signature-verification provider — the batching seam.

The reference hardwires per-signature verification into a sequential loop
(reference: core/.../transactions/SignedTransaction.kt:83-87, engine built at
core/.../crypto/CryptoUtilities.kt:63-96) and its whitepaper calls signature
checking the embarrassingly-parallel hotspot (docs/source/whitepaper/
corda-technical-whitepaper.tex:1597-1604). This module introduces the seam the
reference lacks: everything that checks signatures goes through a
BatchVerifier, so swapping the CPU oracle for the vmap'd JAX/TPU kernel
(corda_tpu/ops/ed25519_jax.py) is a provider change, not a call-site change —
the capability the reference gates behind CordaPluginRegistry-style plugins.

Providers:
  CpuVerifier  — per-signature pure-Python oracle; the conformance authority.
  JaxVerifier  — batched JAX kernel (CPU backend in tests, TPU in prod), with
                 optional shadow sampling: a fraction of batch results is
                 re-checked against the oracle so TPU divergence is detected
                 in production (SURVEY.md §7 hard part #5).

ECDSA P-256: the reference snapshot hardwires Ed25519 for every ledger
signature (its "ECDSA"-named helpers construct EdDSAEngine, reference:
core/src/main/kotlin/net/corda/core/crypto/CryptoUtilities.kt:63-96; no
pluggable SignatureScheme SPI at 0.7); secp256r1 appears ONLY in TLS/X.509
plumbing (core/.../crypto/X509Utilities.kt:44-48). The seam nonetheless
exists here: VerifyJob carries a `scheme` tag, mixed batches split by scheme
(ed25519 → the batched kernel path, ecdsa-p256 → the OpenSSL host fast path
in crypto/fast_ecdsa_p256.py, whose accept set is pinned to the oracle in
crypto/ref_ecdsa_p256.py by an oracle-owned structural gate) and recombine
in order. A device ECDSA kernel can slot behind the same tag if a workload
ever warrants it — today none does.
"""

from __future__ import annotations

import operator
import os
import random
from dataclasses import dataclass
from typing import NoReturn, Sequence

import numpy as np

from ..obs import trace as _obs
from . import fast_ed25519, ref_ed25519


@dataclass(frozen=True)
class VerifyJob:
    """One signature check: does `sig` by `pubkey` cover `message`?

    scheme routes the job: "ed25519" (every ledger signature — the batched
    kernel path) or "ecdsa-p256" (the TLS/X.509 scheme, reference:
    core/.../crypto/X509Utilities.kt:44-48 — host oracle path). Mixed-scheme
    batches split by scheme and recombine in order; unknown schemes reject.
    """

    pubkey: bytes
    message: bytes
    sig: bytes
    scheme: str = "ed25519"


_SCHEME = operator.attrgetter("scheme")


def _all_ed25519(jobs: Sequence[VerifyJob]) -> bool:
    """Whether every job is Ed25519: the firehose and notary shape, which
    skips the scheme split."""
    return list(map(_SCHEME, jobs)).count("ed25519") == len(jobs)


def _dispatch_mixed(jobs: Sequence[VerifyJob], ed25519_fn,
                    p256_fn=None, split: bool | None = None) -> np.ndarray:
    """Verify a batch by scheme. An all-Ed25519 batch goes to `ed25519_fn`
    (each provider's batched path) whole. Otherwise (`split`, computed
    here when not given) the batch splits: the ed25519 subset goes to
    `ed25519_fn`; ecdsa-p256 jobs verify through `p256_fn` (default: the
    OpenSSL fast path with oracle-exact semantics,
    crypto/fast_ecdsa_p256.py); unknown schemes reject. Results recombine
    in input order."""
    if split is None:
        split = not _all_ed25519(jobs)
    if not split:
        return ed25519_fn(jobs) if len(jobs) else np.zeros(0, bool)
    if p256_fn is None:
        from . import fast_ecdsa_p256

        p256_fn = fast_ecdsa_p256.verify
    with _obs.span("verify.prepare"):
        out = np.zeros(len(jobs), bool)
        schemes = list(map(_SCHEME, jobs))
        ed_idx = [i for i, s in enumerate(schemes) if s == "ed25519"]
        ed_jobs = [jobs[i] for i in ed_idx]
    if ed_idx:
        ed_ok = ed25519_fn(ed_jobs)
    with _obs.span("verify.scatter"):
        if ed_idx:
            out[ed_idx] = ed_ok
        for i, s in enumerate(schemes):
            if s == "ecdsa-p256":
                job = jobs[i]
                out[i] = p256_fn(job.pubkey, job.message, job.sig)
    return out


def _columns(jobs: Sequence[VerifyJob]) -> tuple[list, list, list]:
    """The mesh tier's input: jobs as key, message and signature lists."""
    with _obs.span("verify.prepare"):
        return ([j.pubkey for j in jobs], [j.message for j in jobs],
                [j.sig for j in jobs])


class BatchVerifier:
    """Interface: verify many independent signatures at once."""

    name = "abstract"

    def verify_batch(self, jobs: Sequence[VerifyJob]) -> np.ndarray:
        """Returns bool[N]; malformed input rejects (False), never raises."""
        raise NotImplementedError


class CpuVerifier(BatchVerifier):
    """Batched host path with oracle-exact semantics.

    Fast tier: the native libcrypto core (`native/_cverify.c`) verifies the
    whole ed25519 batch in C with the GIL RELEASED — transport readers,
    bridges and the round's sqlite work keep running during a flush, which
    the per-signature Python loop (holding the GIL throughout) prevented.
    Accept-fast only: anything it rejects is re-checked through
    fast_ed25519 (OpenSSL retry, then the authoritative pure-Python
    oracle), so accept/reject stays bit-identical to ref_ed25519 — e.g.
    S >= L signatures, which OpenSSL rejects and the oracle accepts by
    design. Falls back to the Python loop when no toolchain/libcrypto."""

    name = "cpu-openssl"

    def verify_batch(self, jobs: Sequence[VerifyJob]) -> np.ndarray:
        return _dispatch_mixed(jobs, self._verify_ed25519_host)

    @staticmethod
    def _verify_ed25519_host(ed: Sequence[VerifyJob]) -> np.ndarray:
        native = _cverify_module()
        if native is None:
            return np.array(
                [fast_ed25519.verify(j.pubkey, j.message, j.sig)
                 for j in ed], bool)
        accepted = native.verify_many([j.pubkey for j in ed],
                                      [j.message for j in ed],
                                      [j.sig for j in ed])
        out = np.frombuffer(accepted, np.uint8).astype(bool)
        for i in np.flatnonzero(~out):
            # Native-reject is not authoritative: the oracle owns the
            # accept set (rejects are rare on honest traffic, so this
            # stays off the hot path).
            out[i] = fast_ed25519.verify(
                ed[i].pubkey, ed[i].message, ed[i].sig)
        return out


_CVERIFY_CACHE: list = []


def _cverify_module():
    if not _CVERIFY_CACHE:
        try:
            from ..native import load_cverify

            _CVERIFY_CACHE.append(load_cverify())
        except Exception:
            _CVERIFY_CACHE.append(None)
    return _CVERIFY_CACHE[0]


class OracleVerifier(BatchVerifier):
    """Pure-Python oracle loop — THE accept/reject conformance authority.
    Deliberately slow; for conformance tests and shadow checks."""

    name = "cpu-oracle"

    def verify_batch(self, jobs: Sequence[VerifyJob]) -> np.ndarray:
        from . import ref_ecdsa_p256

        return _dispatch_mixed(jobs, lambda ed: np.array(
            [ref_ed25519.verify(j.pubkey, j.message, j.sig) for j in ed],
            bool,
        ), p256_fn=ref_ecdsa_p256.verify)


def _shadow_check(jobs: Sequence[VerifyJob], out: np.ndarray,
                  shadow_rate: float, rng: random.Random) -> None:
    """Re-verify a sample of kernel results on the CPU oracle; a mismatch
    raises RuntimeError (divergence must never be silent)."""
    if shadow_rate <= 0.0:
        return
    for i in range(len(jobs)):
        if rng.random() < shadow_rate:
            want = ref_ed25519.verify(
                jobs[i].pubkey, jobs[i].message, jobs[i].sig)
            if bool(out[i]) != want:
                raise RuntimeError(
                    f"TPU/CPU verify divergence at index {i}: "
                    f"kernel={bool(out[i])} oracle={want}")


# Below this many ed25519 jobs a device round trip loses to the host path:
# the kernel pads every batch to >=1024 lanes and pays ~ms of pack+dispatch
# +readback per call, while the native/OpenSSL host tier verifies small
# batches in tens of microseconds (bench trader_dvp once measured 0.79
# trades/s device-always vs ~120 host, over a slow link to the chip; not
# re-measured on a directly attached chip). This is routing by size, not
# a fallback. Overridable per verifier or via CORDA_TPU_DEVICE_MIN_SIGS;
# 0 forces device-always.
DEVICE_MIN_SIGS_DEFAULT = 512


def _resolve_device_min_sigs(value: int | None) -> int:
    """Shared constructor policy for the size crossover (JaxVerifier and
    MeshVerifier): explicit argument wins, else CORDA_TPU_DEVICE_MIN_SIGS,
    else the measured default."""
    if value is not None:
        return value
    return int(os.environ.get(
        "CORDA_TPU_DEVICE_MIN_SIGS", DEVICE_MIN_SIGS_DEFAULT))


class DeviceRoutedVerifier(BatchVerifier):
    """Shared routing policy for the device-backed verifiers: the size
    crossover (batches under device_min_sigs take the host tier), the
    boot-warm device_gate (batches host-route while a warm-up is in
    flight — the first kernel call in a process pays backend init +
    compile, measured stalling a notary ~100 s in-loop), and the
    host/device batch counters every stamp reads. Subclasses implement
    the device dispatch (_verify_ed25519_device) and warm()."""

    def __init__(self, shadow_rate: float = 0.0,
                 rng: random.Random | None = None,
                 device_min_sigs: int | None = None):
        self.shadow_rate = shadow_rate
        self._rng = rng or random.Random(0)
        # Runtime-tunable: async_verify.AdaptiveCrossover rewrites this
        # from observed host- vs device-tier sigs/s; the resolved value is
        # only the starting point. Reads/writes stay single-threaded (the
        # run loop owns routing policy; the feeder thread only reads it
        # inside verify_batch — a stale read routes one batch, never
        # corrupts state).
        self.device_min_sigs = _resolve_device_min_sigs(device_min_sigs)
        self.host_batches = 0
        self.device_batches = 0
        # Batches of more than one scheme, which split by scheme and merge
        # back (the all-Ed25519 shape goes to the ed25519 path whole).
        self.split_batches = 0
        # node.py _warm_verifier_maybe installs its done-event here;
        # None (the default) means no gate. degrade_device() reuses the
        # same gate to host-route while the device tier is suspect.
        self.device_gate = None
        # Degrade bookkeeping (degrade_device): times the device tier was
        # demoted after a failure, and re-probe outcomes.
        self.degraded = 0
        self.reprobes_ok = 0
        self.reprobes_failed = 0
        self._reprobe_thread = None

    def verify_batch(self, jobs: Sequence[VerifyJob]) -> np.ndarray:
        if not jobs:
            return np.zeros(0, bool)
        split = not _all_ed25519(jobs)
        self.split_batches += split
        with _obs.span("verify.batch", lanes=len(jobs), split=int(split)):
            return _dispatch_mixed(jobs, self._verify_ed25519, split=split)

    def _verify_ed25519(self, jobs: Sequence[VerifyJob]) -> np.ndarray:
        if (len(jobs) < self.device_min_sigs
                or (self.device_gate is not None
                    and not self.device_gate.is_set())):
            # Host tier is oracle-exact by construction (CpuVerifier doc);
            # no shadow sampling needed on this route.
            self.host_batches += 1
            return CpuVerifier._verify_ed25519_host(jobs)
        self.device_batches += 1
        out = self._verify_ed25519_device(jobs)
        _shadow_check(jobs, out, self.shadow_rate, self._rng)
        return out

    def _verify_ed25519_device(self, jobs: Sequence[VerifyJob]) -> np.ndarray:
        raise NotImplementedError

    def pack_device(self, jobs: Sequence[VerifyJob]):
        """Split seam for pipelined callers (the sidecar's double-buffered
        executor): host-side columnar packing of a batch, separable from the
        device dispatch, so batch N+1 packs while batch N runs on the
        device. Returns an opaque handle for :meth:`verify_packed`, or None
        when this batch would NOT take the device tier (size/gate routing
        says host, mixed schemes, nothing well-formed) — the caller then
        falls back to the ordinary verify_batch path, which routes
        identically. Base verifiers don't support the split."""
        return None

    def verify_packed(self, packed) -> np.ndarray:
        """Dispatch a handle produced by :meth:`pack_device`. Counts as a
        device batch (routing was already decided at pack time)."""
        raise NotImplementedError

    def warm(self) -> None:
        """Compile THIS verifier's device path at both pump bucket sizes,
        bypassing the gate/size routing. Blocking and exception-raising —
        the caller (node.py boot warm-up) owns gating and error policy."""
        raise NotImplementedError


# Warm batch sizes covering the pump's REAL bucket ladder on every backend:
# 513 -> bucket 1024 (the smallest batch the size crossover sends to the
# device, with or without the Pallas >=1024 pad) and 1025 -> bucket 4096
# (backlogged rounds reach max_sigs=4096). A 1-sig warm would compile
# bucket 64 under plain XLA — a graph the pump never uses — leaving the
# 1024 bucket cold exactly when Pallas is unavailable.
WARM_SIZES = (513, 1025)


class JaxVerifier(DeviceRoutedVerifier):
    """Batched JAX kernel with shadow-sampled oracle cross-checks.

    shadow_rate: fraction of results re-verified on the CPU oracle; a mismatch
    raises RuntimeError (divergence must never be silent).

    Batches below device_min_sigs route to the HOST tier (same semantics:
    CpuVerifier's accept-fast + oracle-authoritative path) — the per-batch
    backend choice by size, mirroring hash_many_auto's crossover constant.
    host_batches/device_batches count where work actually went so bench
    stamps and node metrics can attribute every number.
    """

    name = "jax-batch"

    def _verify_ed25519_device(self, jobs: Sequence[VerifyJob]) -> np.ndarray:
        from ..ops import ed25519_jax

        return ed25519_jax.verify_jobs(jobs)

    def warm(self) -> None:
        from ..ops import ed25519_jax

        for n in WARM_SIZES:
            ed25519_jax.verify_batch([bytes(32)] * n, [bytes(32)] * n,
                                     [bytes(64)] * n)


class MeshVerifier(DeviceRoutedVerifier):
    """SPMD verify over a device mesh: the batch axis of every verify batch
    is sharded across the local devices with shard_map (ops/sharded.py), so
    a multi-chip slice verifies one notary batch cooperatively — the
    whitepaper's "signatures can easily be verified in parallel" realised
    across chips (reference: docs/source/whitepaper/
    corda-technical-whitepaper.tex:1597-1604).

    Selectable as ``verifier = "jax-sharded"`` in node config or
    CORDA_TPU_VERIFIER. The mesh spans all local devices by default
    (n_devices limits it); construction is lazy so importing the provider
    costs nothing on hosts without an initialised backend.
    """

    name = "jax-sharded"

    def __init__(self, n_devices: int | None = None,
                 shadow_rate: float = 0.0,
                 rng: random.Random | None = None,
                 device_min_sigs: int | None = None):
        super().__init__(shadow_rate=shadow_rate, rng=rng,
                         device_min_sigs=device_min_sigs)
        self.n_devices = n_devices
        self._mesh = None

    @property
    def mesh(self):
        if self._mesh is None:
            from ..ops import sharded

            # lint: allow(no-jit-in-hotpath) lazy one-time constructor: the mesh is built once and memoised on self._mesh; per-batch calls only read the cached object
            self._mesh = sharded.make_mesh(self.n_devices)
        return self._mesh

    def _verify_ed25519_device(self, jobs: Sequence[VerifyJob]) -> np.ndarray:
        from ..ops import sharded

        return sharded.verify_batch_sharded(*_columns(jobs), self.mesh)

    def pack_device(self, jobs: Sequence[VerifyJob]):
        """Host half of the mesh dispatch, routed EXACTLY like
        _verify_ed25519: batches the size/gate crossover would host-route
        return None (so the pipelined caller's fallback lands on the same
        tier this verifier would have chosen), as do mixed-scheme batches
        (the split path only accelerates the pure-ed25519 firehose shape)
        and all-malformed batches (the host tier answers those for free)."""
        if (not jobs
                or len(jobs) < self.device_min_sigs
                or (self.device_gate is not None
                    and not self.device_gate.is_set())
                or not _all_ed25519(jobs)):
            return None
        from ..ops import sharded

        return sharded.pack_batch_sharded(*_columns(jobs), self.mesh)

    def verify_packed(self, packed) -> np.ndarray:
        from ..ops import sharded

        self.device_batches += 1
        return sharded.dispatch_packed(packed)

    def warm(self) -> None:
        """Compile the SHARDED graphs this verifier actually dispatches
        (warming the single-chip kernel would open the gate without the
        mesh path ever compiling)."""
        from ..ops import sharded

        for n in WARM_SIZES:
            sharded.verify_batch_sharded([bytes(32)] * n, [bytes(32)] * n,
                                         [bytes(64)] * n, self.mesh)


# Exit status of a process whose device verifier failed its warm-up on
# an accelerator (node boot, sidecar start).
WARM_FAILED_EXIT = 70


def exit_on_warm_failure(owner: str) -> NoReturn:
    """End THIS process: a device-backed verifier failed to warm on an
    accelerator. Serving on from the host tier would hide the failure
    behind a quietly slower system for the process's whole life, so the
    failure is logged with its traceback (call from an ``except`` block)
    and the process exits with WARM_FAILED_EXIT."""
    import logging
    import sys

    logging.getLogger("corda_tpu.verify").critical(
        "%s: verifier warm-up failed on the accelerator; exiting with "
        "status %d", owner, WARM_FAILED_EXIT, exc_info=True)
    sys.stderr.flush()
    os._exit(WARM_FAILED_EXIT)


def host_verify(jobs: Sequence[VerifyJob]) -> np.ndarray:
    """Verify a batch on the host tier regardless of any verifier's routing
    state — the degrade path's re-verify (oracle-exact accept set, so a
    batch the device would have accepted is accepted here too)."""
    return _dispatch_mixed(jobs, CpuVerifier._verify_ed25519_host)


# Seconds a degraded device tier stays demoted before the background
# re-probe tries the device path again.
DEVICE_REPROBE_COOLDOWN_S_DEFAULT = 5.0


def degrade_device(verifier, cooldown_s: float | None = None) -> bool:
    """Demote a device-backed verifier to its host tier after a device-path
    failure, and schedule a cooldown re-probe that re-opens the gate once
    the device answers again.

    Closes (or installs) ``verifier.device_gate`` — every future batch
    host-routes — then starts a daemon thread that sleeps ``cooldown_s``
    (default ``CORDA_TPU_DEVICE_REPROBE_COOLDOWN_S`` or 5 s), runs the
    verifier's own device path on a throwaway batch, and sets the gate on
    success; on failure it keeps the gate closed and retries after another
    cooldown. Returns False (no-op) for verifiers without a device tier.
    Safe to call repeatedly: a second failure while a re-probe is pending
    only bumps the counter."""
    if getattr(verifier, "device_min_sigs", None) is None:
        return False
    import threading
    import time as _t

    gate = getattr(verifier, "device_gate", None)
    if gate is None:
        gate = threading.Event()
        verifier.device_gate = gate
    probing = getattr(verifier, "_reprobe_thread", None)
    already_probing = (not gate.is_set() and probing is not None
                       and probing.is_alive())
    gate.clear()
    verifier.degraded = getattr(verifier, "degraded", 0) + 1
    if already_probing:
        return True
    if cooldown_s is None:
        cooldown_s = float(os.environ.get(
            "CORDA_TPU_DEVICE_REPROBE_COOLDOWN_S",
            DEVICE_REPROBE_COOLDOWN_S_DEFAULT))

    def _reprobe() -> None:
        # Garbage jobs: the probe cares that the device path ANSWERS (an
        # all-False result is fine), not that signatures validate.
        n = max(2, int(getattr(verifier, "device_min_sigs", 2) or 2))
        probe = [VerifyJob(bytes(32), bytes(32), bytes(64))] * n
        while not gate.is_set():
            _t.sleep(cooldown_s)
            try:
                verifier._verify_ed25519_device(probe)
            except Exception:
                verifier.reprobes_failed = getattr(
                    verifier, "reprobes_failed", 0) + 1
                continue
            verifier.reprobes_ok = getattr(verifier, "reprobes_ok", 0) + 1
            gate.set()

    t = threading.Thread(target=_reprobe, daemon=True, name="verify-reprobe")
    verifier._reprobe_thread = t
    t.start()
    return True


_default: BatchVerifier | None = None


def get_verifier() -> BatchVerifier:
    """The process-wide verifier. Defaults from CORDA_TPU_VERIFIER
    (cpu | jax | jax-shadow); cpu if unset."""
    global _default
    if _default is None:
        choice = os.environ.get("CORDA_TPU_VERIFIER", "cpu")
        _default = make_verifier(choice)
    return _default


def make_verifier(kind: str) -> BatchVerifier:
    """Provider factory shared by the env default and NodeConfig.verifier:
    cpu | jax | jax-shadow | jax-sharded. Unknown names raise — a typo
    must not silently demote a notary to the CPU path."""
    if kind == "jax":
        return JaxVerifier()
    if kind == "jax-shadow":
        return JaxVerifier(shadow_rate=0.05)
    if kind == "jax-sharded":
        return MeshVerifier()
    if kind == "cpu":
        return CpuVerifier()
    raise ValueError(
        f"unknown verifier {kind!r}: expected cpu | jax | jax-shadow | "
        "jax-sharded")


def set_verifier(verifier: BatchVerifier | None) -> None:
    """Install a provider (None resets to environment default)."""
    global _default
    _default = verifier
