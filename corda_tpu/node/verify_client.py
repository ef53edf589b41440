"""SidecarVerifier — the node-side client of the verification sidecar.

Plugs into the existing BatchVerifier seam unchanged: node assembly swaps it
in for the local provider when ``[batch] sidecar`` (or CORDA_TPU_SIDECAR)
names a server address, and the async feeder, SMM degrade path, metrics
stamps and adaptive crossover all keep working by duck type — it IS a
DeviceRoutedVerifier whose "device" is the host-local sidecar socket.

The crossover default is deliberately LOW (16, not 512): shipping a
micro-batch to the sidecar costs one local-socket round trip, and the
sidecar amortises the REAL device dispatch across every node process on the
host. Per-process batching (512 floor) is exactly what left device_batches
at 0 on the round-5 flagship; the sidecar exists so micro-batches flow out
and coalesce server-side.

Failure policy — never a wrong answer, never a hang:
  * Any transport/deadline/protocol failure raises SidecarError from
    ``_verify_ed25519_device``. The routing override catches it, demotes
    the sidecar tier through provider.degrade_device (shared gate +
    cooldown re-probe machinery) and answers the batch from the local host
    tier, which is oracle-exact. Infra faults degrade; they never reject.
  * The cooldown re-probe calls ``_verify_ed25519_device`` directly with a
    garbage batch and interprets "no exception" as healthy — which is why
    the device method must RAISE on failure rather than falling back
    internally: an internal fallback would re-open the gate while the
    sidecar is still dead.
"""

from __future__ import annotations

import socket
import struct
import threading
import time
from typing import Sequence

import numpy as np

from ..crypto import sidecar as wire
from ..crypto.provider import DeviceRoutedVerifier, VerifyJob, degrade_device

# Size crossover for the SIDECAR tier (see module doc: low on purpose —
# the expensive device round trip happens server-side, amortised across
# processes; the client only pays a local socket RTT).
SIDECAR_MIN_SIGS_DEFAULT = 16


class SidecarError(RuntimeError):
    """The sidecar failed to answer: dead, deadline missed, or protocol
    error. Carries no verdicts — the caller re-verifies on the host."""


class SidecarVerifier(DeviceRoutedVerifier):
    """Verifies ed25519 batches through the per-host sidecar server."""

    name = "sidecar"  # must NOT start with "jax": the node's local warm
    #                   path and jax-only stamping do not apply here
    MIN_SIGS_ENV = "CORDA_TPU_SIDECAR_MIN_SIGS"
    MIN_SIGS_DEFAULT = SIDECAR_MIN_SIGS_DEFAULT

    def __init__(self, address: str, deadline_ms: float = 2000.0,
                 device_min_sigs: int | None = None,
                 connect_timeout_s: float = 1.0,
                 reprobe_cooldown_s: float | None = None,
                 devices: int | None = None):
        super().__init__(device_min_sigs=device_min_sigs)
        self.address = address
        self.deadline_s = float(deadline_ms) / 1e3
        self.connect_timeout_s = connect_timeout_s
        self.reprobe_cooldown_s = reprobe_cooldown_s
        # Mesh width the config SAYS the server owns ([batch]
        # sidecar_devices): stamped for attribution; the server snapshot
        # below carries the proven value.
        self.devices = devices or None
        # Server-stats cache keyed BY ENDPOINT, not a single slot: the
        # federation router (crypto/federation.py) holds one client per
        # host, and any address change (or a future shared cache) must
        # never serve one sidecar's stale snapshot as another's.
        self._server_snapshots: dict[str, tuple[float, dict | None]] = {}
        self._sock: socket.socket | None = None
        self._req_id = 0
        # Serialises the socket: the feeder thread and the degrade
        # re-probe thread may both round-trip; one framed request/reply
        # pair at a time keeps req_id matching trivial.
        self._io_lock = threading.Lock()
        self.sidecar_batches = 0
        self.sidecar_sigs = 0
        self.fallbacks = 0
        self.connects = 0
        self.rpc_s_total = 0.0
        # Server-reported timings of the newest answered batch; the async
        # feeder turns these into sidecar_wait/sidecar_verify spans.
        self.last_wait_s: float | None = None
        self.last_verify_s: float | None = None
        self.last_tier: str | None = None
        # QoS hint: (lane_code, deadline_ns) set by the SMM right before a
        # flush when the queued micro-batch contains an interactive request
        # with a live deadline. Advisory and racy-by-design — a stale hint
        # costs one early server flush, never correctness. When set, the
        # next batch ships as OP_VERIFY_QOS so the sidecar's deadline
        # scheduler can order/flush around it.
        self.qos_hint: tuple[int, int] | None = None

    def reset_window(self) -> None:
        """Cache-bust seam for back-to-back measurements (the autotune
        controller calls this between sweep candidates): drop every
        cached server snapshot so the next stats ride fetches fresh —
        the 5 s TTL would otherwise hand candidate N the stats of
        candidate N-1."""
        self._server_snapshots.clear()

    # -- routing ------------------------------------------------------------

    def _verify_ed25519(self, jobs: Sequence[VerifyJob]) -> np.ndarray:
        if self._host_routed(jobs):
            return self._verify_on_host(jobs)
        try:
            out = self._verify_ed25519_device(jobs)
        except SidecarError:
            # Hard fallback: demote the sidecar tier (gate + cooldown
            # re-probe) and answer from the oracle-exact host path.
            self.fallbacks += 1
            degrade_device(self, cooldown_s=self.reprobe_cooldown_s)
            return self._verify_on_host(jobs)
        self.device_batches += 1
        return out

    # -- the sidecar round trip --------------------------------------------

    def _verify_ed25519_device(self, jobs: Sequence[VerifyJob]) -> np.ndarray:
        """One framed OP_VERIFY round trip. Raises SidecarError on ANY
        failure — this method doubles as the degrade re-probe ("the
        sidecar answered a batch" == healthy), so it must never fall back
        internally."""
        # Wrong-length keys/sigs can't ride the fixed-width wire arrays;
        # they reject locally — identical semantics to the kernel path
        # (malformed input rejects, never raises).
        good_idx = [i for i, j in enumerate(jobs)
                    if len(j.pubkey) == 32 and len(j.sig) == 64]
        out = np.zeros(len(jobs), bool)
        if not good_idx:
            return out
        good = (list(jobs) if len(good_idx) == len(jobs)
                else [jobs[i] for i in good_idx])
        t0 = time.perf_counter()
        # lint: allow(no-blocking-under-lock) _io_lock exists to serialize request/reply framing on the one sidecar socket; callers that must not queue here use their own client instance
        with self._io_lock:
            deadline = time.perf_counter() + self.deadline_s
            try:
                sock = self._connect_maybe()
                self._req_id += 1
                req_id = self._req_id
                sock.settimeout(max(0.05, deadline - time.perf_counter()))
                hint = self.qos_hint
                if hint is not None:
                    lane_code, deadline_ns = hint
                    req = wire.encode_verify_request_qos(
                        req_id, good, lane_code, deadline_ns)
                else:
                    req = wire.encode_verify_request(req_id, good)
                wire.send_frame(sock, req)
                while True:
                    sock.settimeout(max(0.05,
                                        deadline - time.perf_counter()))
                    payload = wire.recv_frame(sock)
                    (op, rid, status, tier, wait_s,
                     verify_s) = wire._VERIFY_REPLY_HDR.unpack_from(payload)
                    if op == wire.OP_VERIFY and rid == req_id:
                        break  # anything else is a stale/odd frame: skip
                if status != wire.STATUS_OK:
                    detail = payload[wire._VERIFY_REPLY_HDR.size:].decode(
                        errors="replace")
                    raise SidecarError(
                        f"sidecar verify failed: {detail or 'error'}")
                flags = np.frombuffer(
                    payload, np.uint8,
                    offset=wire._VERIFY_REPLY_HDR.size).astype(bool)
                if len(flags) != len(good):
                    raise SidecarError("short sidecar reply")
            except (OSError, ConnectionError, socket.timeout, struct.error,
                    ValueError) as exc:
                # Half-answered streams can't be resumed; reconnect fresh
                # next time (also what makes the re-probe meaningful).
                self._drop_connection()
                raise SidecarError(
                    f"sidecar {self.address}: {exc}") from exc
        self.sidecar_batches += 1
        self.sidecar_sigs += len(good)
        self.rpc_s_total += time.perf_counter() - t0
        self.last_wait_s = float(wait_s)
        self.last_verify_s = float(verify_s)
        self.last_tier = "device" if tier else "host"
        if len(good_idx) == len(jobs):
            return flags
        out[good_idx] = flags
        return out

    def warm(self) -> None:
        """Ping the server (connectivity check; nothing to compile on the
        client side — the SERVER owns device warm-up)."""
        # lint: allow(no-blocking-under-lock) same socket-framing serialization lock as verify_batch: the ping must not interleave with an in-flight verify frame
        with self._io_lock:
            try:
                sock = self._connect_maybe()
                self._req_id += 1
                sock.settimeout(self.connect_timeout_s)
                wire.send_frame(
                    sock, wire._REQ_HDR.pack(wire.OP_PING, self._req_id))
                wire.recv_frame(sock)
            except (OSError, ConnectionError, struct.error) as exc:
                self._drop_connection()
                raise SidecarError(
                    f"sidecar {self.address}: {exc}") from exc

    def _connect_maybe(self) -> socket.socket:
        if self._sock is None:
            self._sock = wire.connect(self.address,
                                      timeout=self.connect_timeout_s)
            self.connects += 1
        return self._sock

    def _drop_connection(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    # -- stamping -----------------------------------------------------------

    def sidecar_stats(self) -> dict:
        """Client-side view for node_metrics / loadtest node_stamps."""
        gate = self.device_gate
        return {
            "address": self.address,
            "deadline_ms": self.deadline_s * 1e3,
            "min_sigs": self.device_min_sigs,
            "batches": self.sidecar_batches,
            "sigs": self.sidecar_sigs,
            "fallbacks": self.fallbacks,
            "connects": self.connects,
            "rpc_s_total": round(self.rpc_s_total, 6),
            "last_wait_s": self.last_wait_s,
            "last_verify_s": self.last_verify_s,
            "last_tier": self.last_tier,
            "gate_open": gate.is_set() if gate is not None else None,
            "degraded": self.degraded,
            "reprobes_ok": self.reprobes_ok,
            "reprobes_failed": self.reprobes_failed,
            "devices": self.devices,
            "server": self._server_stats_maybe(),
        }

    def _server_stats_maybe(self) -> dict | None:
        """Best-effort server-side snapshot (per-device occupancy, pad
        fraction, mesh size) riding the client stamp into node_metrics —
        fetched over a FRESH connection so it never contends with an
        in-flight verify, cached 5 s PER ENDPOINT so metrics polls stay
        cheap without one sidecar's snapshot masquerading as another's,
        and None (never an exception) when the server is unreachable."""
        now = time.monotonic()
        hit = self._server_snapshots.get(self.address)
        if hit is not None and now - hit[0] < 5.0:
            return hit[1]
        try:
            snap = fetch_sidecar_stats(self.address, timeout=0.5)
        except SidecarError:
            snap = None
        self._server_snapshots[self.address] = (now, snap)
        return snap


def fetch_sidecar_stats(address: str, timeout: float = 2.0) -> dict:
    """One-shot OP_STATS round trip on a fresh connection — harness-side
    artifact gathering (loadtest/bench). Raises SidecarError when the
    server is unreachable."""
    try:
        sock = wire.connect(address, timeout=timeout)
        try:
            sock.settimeout(timeout)
            wire.send_frame(sock, wire._REQ_HDR.pack(wire.OP_STATS, 1))
            payload = wire.recv_frame(sock)
            op, _, status = wire._REPLY_HDR.unpack_from(payload)
            if op != wire.OP_STATS or status != wire.STATUS_OK:
                raise ValueError("bad sidecar stats reply")
            import json

            return json.loads(payload[wire._REPLY_HDR.size:].decode())
        finally:
            try:
                sock.close()
            except OSError:
                pass
    except (OSError, ConnectionError, ValueError, struct.error) as exc:
        raise SidecarError(f"sidecar {address}: {exc}") from exc
