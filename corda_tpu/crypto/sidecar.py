"""Device-owning verification sidecar: cross-process batch coalescing.

The round-5 flagship gap: the Pallas kernel streams ~292k sigs/s, but the
raft-validating multiprocess loadtest delivered 3.9k sigs/s with
``device_batches=0`` — each node PROCESS accumulates its own micro-batches,
every one below device_min_sigs, so all traffic host-routed and the device
sat idle on exactly the path BASELINE.json measures. Per-process batching
cannot fix this: the batches are small because each run loop only sees its
own flows.

This module is the missing seam the north-star design prescribes (PAPER §7:
micro-batches ship "over a JNI/gRPC bridge to a JAX sidecar" owning the
accelerator): ONE verification server per host, fed by every node process
over a local socket, coalescing requests ACROSS processes before dispatch —
clipper/serving-style adaptive batching (PAPERS.md).

Server structure (mirrors async_verify.py's pipeline, one level up):
  reader threads   — one per client connection; decode framed requests into
                     a shared pending queue.
  scheduler thread — deadline-based coalescing: holds the queue open from
                     the FIRST pending request for up to coalesce_us,
                     flushing early when pending sigs reach max_sigs
                     (bucket capacity). Whole requests only — a request is
                     never split across batches, so per-client replies stay
                     one frame. After forming a batch it also runs the
                     HOST half of the device dispatch (pack_device:
                     columnar packing into padded kernel arrays), so
                     packing batch N+1 overlaps device execution of
                     batch N on the executor thread.
  executor thread  — dispatches the pre-packed arrays (verify_packed) or,
                     for host-routed/unpackable batches, one verify_batch
                     call on the server's verifier (the
                     DeviceRoutedVerifier size/gate routing and the padded
                     pick_bucket executable cache in ops/ed25519_jax are
                     reused unchanged), then splits results per request.
  depth-2 buffering: a BoundedSemaphore(depth) between scheduler and
                     executor lets the scheduler coalesce AND pack the
                     NEXT batch while the current one runs on the device.

Mesh ownership (round 10): ``devices=N`` makes the server own a JAX device
mesh instead of one chip — the verifier becomes a MeshVerifier whose
coalesced buckets are sharded data-parallel across the N local devices
(ops/sharded.py shard_map with fixed in/out shardings, so repeated
dispatches reuse one executable per bucket and never re-partition). The
bucket ladder is rounded up to a multiple of the mesh size
(pad_to_devices), every device gets an equal slice, and the pad waste is
attributed in stats (pad_fraction / per_device_occupancy /
per_device_batch_sigs_hist). devices=1 keeps the exact single-device
verifier; a mesh that cannot be built (fewer local devices than asked)
leaves the boot-warm gate closed so every batch takes the oracle-exact
host tier — degraded throughput, never a wrong answer.

Wire protocol — length-prefixed frames over a stream socket (unix path or
host:port), little-endian throughout:
  frame    := u32(len) payload
  request  := u8(op) u32(req_id) body
  OP_VERIFY  body:  u32(n)  pubkeys n*32  sigs n*64  u32 msg_len[n]  msgs
  OP_VERIFY  reply: u8(op) u32(req_id) u8(status) u8(tier)
                    f32(wait_s) f32(verify_s)  u8 ok[n]     (tier: 1=device)
  OP_STATS   reply: u8(op) u32(req_id) u8(status)  json(stats) utf-8
  OP_METRICS reply: u8(op) u32(req_id) u8(status)  prometheus text utf-8
  OP_PING    reply: u8(op) u32(req_id) u8(status)
Only well-formed ed25519 jobs ride the fixed-width arrays; the client
rejects wrong-length keys/sigs locally (same semantics as the kernel path:
malformed input rejects, never raises).

Crash contract: the sidecar holds NO durable state. A dead sidecar is an
infra fault — clients degrade to their local host tier (oracle-exact accept
set) through provider.degrade_device and re-probe on a cooldown; flows
in-flight at the moment of death replay at-least-once like any other verify
infra failure. The sidecar can never make a node commit a wrong answer.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import socket
import struct
import threading
import time
from collections import deque
from typing import Sequence

import numpy as np

from ..obs import telemetry as _tm
from .provider import VerifyJob, exit_on_warm_failure, make_verifier

OP_VERIFY = 1
OP_STATS = 2
OP_PING = 3
# OP_VERIFY with a QoS prefix (lane code + interactive deadline in epoch
# ns): same columnar body, same OP_VERIFY reply. Sent only when the
# client's QoS plane is armed AND its micro-batch carried an interactive
# deadline — a disarmed cluster never emits this op, and a pre-QoS server
# rejects it loudly (unknown op drops the connection, the client degrades
# to its host tier) instead of silently mis-scheduling.
OP_VERIFY_QOS = 4
# Prometheus text exposition of this process's telemetry registry
# (obs/export.py render): the sidecar's /metrics — same framing as
# OP_STATS with a text body instead of JSON.
OP_METRICS = 5

STATUS_OK = 0
STATUS_ERR = 1

# Lane codes on the wire (mirrors qos/context.py; this module stays
# importable without the qos package on pre-QoS peers).
LANE_CODE_INTERACTIVE = 0
LANE_CODE_BULK = 1

# One frame bounds one coalesced request: 64 MiB covers max_sigs=65536 jobs
# of pubkey+sig+len plus ~900-byte messages — far beyond any pump batch.
MAX_FRAME = 64 * 1024 * 1024

_FRAME_HDR = struct.Struct("<I")
_REQ_HDR = struct.Struct("<BI")
_VERIFY_REQ_HDR = struct.Struct("<BII")
# op, req_id, n, lane code, deadline_ns (epoch; 0 = no deadline).
_VERIFY_QOS_REQ_HDR = struct.Struct("<BIIBQ")
_REPLY_HDR = struct.Struct("<BIB")
_VERIFY_REPLY_HDR = struct.Struct("<BIBBff")

# The kernel's padded-bucket ladder (ops/ed25519_jax.pick_bucket), mirrored
# here so the batch-size histogram keys by executable bucket without this
# module ever importing jax (stats must work on host-only processes).
BUCKETS = (64, 256, 1024, 4096, 16384, 65536)


def bucket_for(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return BUCKETS[-1]


def pad_to_devices(n: int, n_devices: int) -> int:
    """Smallest multiple of n_devices >= max(n, n_devices) — mirrored from
    ops/sharded.py (pure arithmetic) for the same reason BUCKETS mirrors
    pick_bucket: pad attribution must work without importing jax."""
    return -(-max(n, 1) // max(n_devices, 1)) * max(n_devices, 1)


# Adaptive coalesce_us policy (ROADMAP item 1: grow the deadline from the
# observed batch-size histogram so the mesh sees full buckets; shrink it
# when batches fill early so p99 never pays for an idle window). Same
# hysteresis/multiplicative-step idiom as async_verify.AdaptiveCrossover.
ADAPT_WINDOW = 8        # executed batches per decision
ADAPT_GROW = 1.5
ADAPT_SHRINK = 0.75
ADAPT_SEED_US = 200     # first growth step out of coalesce_us=0
ADAPT_CEILING_US = 20_000


# ---------------------------------------------------------------------------
# Framing + codec (shared by server and node/verify_client.py)
# ---------------------------------------------------------------------------


def send_frame(sock: socket.socket, payload: bytes) -> None:
    sock.sendall(_FRAME_HDR.pack(len(payload)) + payload)


def recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("sidecar connection closed")
        buf += chunk
    return bytes(buf)


def recv_frame(sock: socket.socket) -> bytes:
    (ln,) = _FRAME_HDR.unpack(recv_exact(sock, _FRAME_HDR.size))
    if ln > MAX_FRAME:
        raise ConnectionError(f"sidecar frame too large: {ln}")
    return recv_exact(sock, ln)


def _encode_jobs(jobs: Sequence[VerifyJob]) -> bytes:
    """Columnar job body shared by both verify ops: the server decodes
    with numpy slices, mirroring the native/_cverify packers."""
    n = len(jobs)
    return b"".join((
        b"".join(bytes(j.pubkey) for j in jobs),
        b"".join(bytes(j.sig) for j in jobs),
        np.fromiter((len(j.message) for j in jobs), "<u4", n).tobytes(),
        b"".join(bytes(j.message) for j in jobs),
    ))


def _decode_jobs(payload: bytes, off: int, n: int) -> list[VerifyJob]:
    pks = payload[off:off + 32 * n]
    off += 32 * n
    sigs = payload[off:off + 64 * n]
    off += 64 * n
    lens = np.frombuffer(payload, "<u4", n, off)
    off += 4 * n
    if len(pks) != 32 * n or len(sigs) != 64 * n:
        raise ValueError("short sidecar verify request")
    jobs = []
    for i in range(n):
        ln = int(lens[i])
        msg = payload[off:off + ln]
        if len(msg) != ln:
            raise ValueError("short sidecar verify request")
        off += ln
        jobs.append(VerifyJob(pks[32 * i:32 * i + 32], msg,
                              sigs[64 * i:64 * i + 64]))
    return jobs


def encode_verify_request(req_id: int, jobs: Sequence[VerifyJob]) -> bytes:
    """Pack well-formed ed25519 jobs (32-byte keys, 64-byte sigs) into one
    OP_VERIFY payload."""
    return _VERIFY_REQ_HDR.pack(OP_VERIFY, req_id, len(jobs)) \
        + _encode_jobs(jobs)


def decode_verify_request(payload: bytes):
    """-> (req_id, [VerifyJob...]); raises on a malformed frame (the reader
    drops the connection — a corrupt stream cannot be resynchronised)."""
    _op, req_id, n = _VERIFY_REQ_HDR.unpack_from(payload)
    return req_id, _decode_jobs(payload, _VERIFY_REQ_HDR.size, n)


def encode_verify_request_qos(req_id: int, jobs: Sequence[VerifyJob],
                              lane: int, deadline_ns: int) -> bytes:
    """OP_VERIFY_QOS: the OP_VERIFY body prefixed with the micro-batch's
    lane and earliest interactive deadline (epoch ns; 0 = none)."""
    return _VERIFY_QOS_REQ_HDR.pack(
        OP_VERIFY_QOS, req_id, len(jobs), lane,
        deadline_ns & 0xFFFFFFFFFFFFFFFF) + _encode_jobs(jobs)


def decode_verify_request_qos(payload: bytes):
    """-> (req_id, [VerifyJob...], lane, deadline_ns); raises on junk."""
    _op, req_id, n, lane, deadline_ns = \
        _VERIFY_QOS_REQ_HDR.unpack_from(payload)
    if lane not in (LANE_CODE_INTERACTIVE, LANE_CODE_BULK):
        raise ValueError(f"unknown sidecar lane code {lane}")
    return (req_id, _decode_jobs(payload, _VERIFY_QOS_REQ_HDR.size, n),
            lane, deadline_ns)


def parse_address(address: str):
    """'host:port' -> ("tcp", (host, port)); anything else is a unix
    socket path."""
    if ":" in address and "/" not in address:
        host, port = address.rsplit(":", 1)
        return "tcp", (host or "127.0.0.1", int(port))
    return "unix", address


def connect(address: str, timeout: float | None = None) -> socket.socket:
    kind, addr = parse_address(address)
    if kind == "tcp":
        sock = socket.create_connection(addr, timeout=timeout)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    else:
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.settimeout(timeout)
        sock.connect(addr)
    return sock


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------


class _Client:
    """One accepted connection. The write lock serialises replies: verify
    replies come from the executor thread while stats/ping replies come
    from the connection's own reader thread."""

    __slots__ = ("conn", "lock")

    def __init__(self, conn: socket.socket):
        self.conn = conn
        self.lock = threading.Lock()

    def reply(self, payload: bytes) -> None:
        # lint: allow(no-blocking-under-lock) this per-client lock EXISTS to serialize frames on one socket (executor vs reader thread); nothing else ever contends on it
        with self.lock:
            send_frame(self.conn, payload)


class _Pending:
    __slots__ = ("client", "req_id", "jobs", "received_at", "lane",
                 "deadline_ns")

    def __init__(self, client: _Client, req_id: int,
                 jobs: list[VerifyJob], lane: int | None = None,
                 deadline_ns: int = 0):
        self.client = client
        self.req_id = req_id
        self.jobs = jobs
        self.received_at = time.perf_counter()
        # QoS prefix from OP_VERIFY_QOS; None/0 for plain OP_VERIFY
        # requests, which schedule exactly as before.
        self.lane = lane
        self.deadline_ns = deadline_ns


_STOP = object()


class SidecarServer:
    """The per-host verification server. One instance owns the device (via
    its verifier); every node process on the host connects as a client."""

    def __init__(self, address: str, verifier=None, verifier_kind: str = "cpu",
                 coalesce_us: int = 2000, max_sigs: int = 4096,
                 depth: int = 2, device_min_sigs: int | None = None,
                 devices: int | None = None,
                 adaptive_coalesce: bool = False,
                 qos_guard_us: int = 2000):
        self.address = address
        self.devices = int(devices or 0)
        if verifier is None:
            verifier = self._make_server_verifier(verifier_kind, self.devices)
        self.verifier = verifier
        if not self.devices:
            self.devices = int(getattr(verifier, "n_devices", None) or 0)
        if device_min_sigs is not None and hasattr(
                self.verifier, "device_min_sigs"):
            self.verifier.device_min_sigs = device_min_sigs
        self.coalesce_us = int(coalesce_us)
        self.coalesce_us_initial = int(coalesce_us)
        self.adaptive_coalesce = bool(adaptive_coalesce)
        self.coalesce_adjustments = 0
        self._win_batches = 0
        self._win_requests = 0
        self._win_sigs = 0
        self.max_sigs = int(max_sigs)
        self.depth = int(depth)
        # Mesh bookkeeping: mesh_devices is the PROVEN mesh size (set by the
        # warm thread once make_mesh succeeds); warm_error records why a
        # device/mesh tier never opened. Pad attribution prefers the packed
        # handle's exact numbers and falls back to arithmetic on these.
        self.mesh_devices: int | None = None
        self.warm_error: str | None = None

        self._pending: deque[_Pending] = deque()
        self._cv = threading.Condition()
        self._exec_q: queue.SimpleQueue = queue.SimpleQueue()
        # Depth-2 double buffering: the scheduler may have up to `depth`
        # batches formed-or-running, so it keeps coalescing the next batch
        # while the executor holds the device.
        self._slots = threading.BoundedSemaphore(self.depth)
        self._stop = threading.Event()
        self._listener: socket.socket | None = None
        self._clients: list[_Client] = []
        self._threads: list[threading.Thread] = []
        self._lock = threading.Lock()  # stats counters
        self.requests = 0
        self.batches = 0
        self.sigs = 0
        self.cross_request_batches = 0
        self.errors = 0
        # Keyed by each dispatch's bucket: an oversized request dispatches
        # in max_sigs slices, each counted on its own.
        self.batch_sigs_hist: dict[int, int] = {}
        self.wait_s_total = 0.0
        self.verify_s_total = 0.0
        # Mesh/pipeline accounting: packed_batches took the split
        # pack-then-dispatch path (packing overlapped the previous batch's
        # device execution); device_lanes counts lanes actually DISPATCHED
        # on the device tier (bucket-padded), pad_lanes the subset carrying
        # no real signature; the per-device histogram keys by each device's
        # lane share per dispatch.
        self.packed_batches = 0
        self.pack_s_total = 0.0
        self.device_lanes = 0
        self.pad_lanes = 0
        self.per_device_batch_sigs_hist: dict[int, int] = {}
        # QoS (OP_VERIFY_QOS): flush when the earliest interactive
        # deadline is this close (converted to ns once), and count how the
        # deadline scheduler behaved.
        self.qos_guard_ns = int(qos_guard_us) * 1000
        self.qos_early_flushes = 0
        self.qos_interactive_requests = 0
        self.qos_bulk_requests = 0

    @staticmethod
    def _make_server_verifier(kind: str, devices: int):
        """devices > 1 upgrades any jax-tier verifier to a mesh-owning
        MeshVerifier over exactly that many local devices; devices <= 1
        keeps the PR-5 single-device tiers bit-identical (``jax`` stays
        JaxVerifier). A cpu verifier ignores devices — there is no device
        tier to shard."""
        if devices > 1 and kind.startswith("jax"):
            from .provider import MeshVerifier

            return MeshVerifier(
                n_devices=devices,
                shadow_rate=0.05 if kind == "jax-shadow" else 0.0)
        return make_verifier(kind)

    # -- lifecycle ----------------------------------------------------------

    def start(self, warm: bool = True) -> "SidecarServer":
        kind, addr = parse_address(self.address)
        if kind == "unix":
            try:
                os.unlink(addr)
            except FileNotFoundError:
                pass
            listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            listener.bind(addr)
        else:
            listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            listener.bind(addr)
            host, port = listener.getsockname()[:2]
            self.address = f"{host}:{port}"  # resolve port 0
        listener.listen(64)
        self._listener = listener
        if warm:
            self._warm_maybe()
        for target, name in ((self._accept_loop, "sidecar-accept"),
                             (self._scheduler, "sidecar-scheduler"),
                             (self._executor, "sidecar-executor")):
            t = threading.Thread(target=target, daemon=True, name=name)
            t.start()
            self._threads.append(t)
        return self

    def _warm_maybe(self) -> None:
        """Same boot-warm contract as node._warm_verifier_maybe: install a
        closed device_gate, compile in the background, open the gate when
        the device answers. Host traffic flows (host-routed) meanwhile. A
        warm-up that fails on an accelerator ends the process
        (provider.exit_on_warm_failure) — the sidecar exists to own the
        device, so it never serves its whole life from the host tier."""
        verifier = self.verifier
        if not getattr(verifier, "name", "").startswith("jax"):
            return
        gate = threading.Event()
        verifier.device_gate = gate
        # Class-level lookup on purpose: `mesh` is a LAZY property that
        # builds the mesh (and raises when the host can't) — probing the
        # instance would pull that raise into start() instead of the warm
        # thread, where it belongs.
        is_mesh = hasattr(type(verifier), "mesh")

        def _warm() -> None:
            backend = None
            try:
                import jax

                backend = jax.default_backend()
                if is_mesh:
                    # The mesh must be PROVEN before the gate opens:
                    # make_mesh raises when fewer local devices exist than
                    # asked for, and an open gate would route every batch
                    # into that raise.
                    self.mesh_devices = int(verifier.mesh.devices.size)
                if backend != "cpu":
                    verifier.warm()
                # else: CPU-backend compiles are cheap; no warm needed
            except Exception as exc:
                self.warm_error = f"{type(exc).__name__}: {exc}"
                if backend != "cpu":
                    exit_on_warm_failure(f"sidecar {self.address}")
                # CPU backend (tests, virtual meshes): a non-mesh verifier
                # opens the gate and the first failing dispatch produces an
                # error REPLY; a mesh that could not be built keeps the gate
                # closed, so every batch takes the oracle-exact host tier.
                if not is_mesh:
                    gate.set()
                return
            gate.set()

        threading.Thread(target=_warm, daemon=True,
                         name="sidecar-warm").start()

    def stop(self) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        self._exec_q.put(_STOP)
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
        with self._lock:
            clients = list(self._clients)
        for c in clients:
            try:
                c.conn.close()
            except OSError:
                pass
        for t in self._threads:
            t.join(timeout=5.0)
        kind, addr = parse_address(self.address)
        if kind == "unix":
            try:
                os.unlink(addr)
            except OSError:
                pass

    # -- connection handling ------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return  # listener closed
            try:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass  # unix sockets have no TCP options
            client = _Client(conn)
            with self._lock:
                self._clients.append(client)
            t = threading.Thread(target=self._serve_conn, args=(client,),
                                 daemon=True, name="sidecar-conn")
            t.start()

    def _serve_conn(self, client: _Client) -> None:
        try:
            while not self._stop.is_set():
                payload = recv_frame(client.conn)
                op, req_id = _REQ_HDR.unpack_from(payload)
                if op in (OP_VERIFY, OP_VERIFY_QOS):
                    if op == OP_VERIFY:
                        _, jobs = decode_verify_request(payload)
                        pend = _Pending(client, req_id, jobs)
                    else:
                        _, jobs, lane, deadline_ns = \
                            decode_verify_request_qos(payload)
                        pend = _Pending(client, req_id, jobs, lane=lane,
                                        deadline_ns=deadline_ns)
                    # Stats counters mutate under _lock (the lock stats()
                    # reads them under) — never under _cv, so the two locks
                    # are never held together and reader threads can't
                    # lose increments against other stats writers.
                    with self._lock:
                        self.requests += 1
                        if pend.lane == LANE_CODE_INTERACTIVE:
                            self.qos_interactive_requests += 1
                        elif pend.lane == LANE_CODE_BULK:
                            self.qos_bulk_requests += 1
                    if _tm.ACTIVE is not None:
                        _tm.inc("sidecar_requests_total")
                    with self._cv:
                        self._pending.append(pend)
                        self._cv.notify_all()
                elif op == OP_STATS:
                    body = json.dumps(self.stats()).encode()
                    client.reply(
                        _REPLY_HDR.pack(OP_STATS, req_id, STATUS_OK) + body)
                elif op == OP_METRICS:
                    from ..obs.export import render_prometheus

                    client.reply(
                        _REPLY_HDR.pack(OP_METRICS, req_id, STATUS_OK)
                        + render_prometheus().encode())
                elif op == OP_PING:
                    client.reply(_REPLY_HDR.pack(OP_PING, req_id, STATUS_OK))
                else:
                    raise ValueError(f"unknown sidecar op {op}")
        except (ConnectionError, OSError, ValueError, struct.error):
            pass  # client went away or sent garbage: drop the connection
        finally:
            try:
                client.conn.close()
            except OSError:
                pass
            with self._lock:
                if client in self._clients:
                    self._clients.remove(client)

    # -- coalescing scheduler ----------------------------------------------

    def _pending_sigs(self) -> int:
        return sum(len(p.jobs) for p in self._pending)

    def _min_interactive_deadline_ns(self) -> int:
        """Earliest interactive deadline among pending requests (0 = none).
        Called under _cv."""
        dl = 0
        for p in self._pending:
            if (p.lane == LANE_CODE_INTERACTIVE and p.deadline_ns > 0
                    and (dl == 0 or p.deadline_ns < dl)):
                dl = p.deadline_ns
        return dl

    def _form_batch(self) -> tuple[list[_Pending], bool]:
        """Take up to max_sigs from pending, whole requests only: a batch
        never grows past max_sigs (the bucket the warm-up compiled) unless
        one request alone is larger. With no bulk requests waiting this is
        a FIFO popleft loop; when both classes wait, interactive (and
        unlabelled) requests pack first — FIFO within each class — so a
        full batch is cut from the latency-sensitive end and bulk rides the
        next one. Returns (batch, any bulk was deferred behind
        interactive). Called under _cv."""
        if not any(p.lane == LANE_CODE_BULK for p in self._pending):
            batch: list[_Pending] = []
            total = 0
            while self._pending and (
                    not batch
                    or total + len(self._pending[0].jobs) <= self.max_sigs):
                p = self._pending.popleft()
                batch.append(p)
                total += len(p.jobs)
            return batch, False
        pending = list(self._pending)
        ordered = ([p for p in pending if p.lane != LANE_CODE_BULK]
                   + [p for p in pending if p.lane == LANE_CODE_BULK])
        batch, taken, total = [], set(), 0
        for p in ordered:
            if batch and total + len(p.jobs) > self.max_sigs:
                break
            batch.append(p)
            taken.add(id(p))
            total += len(p.jobs)
        self._pending = deque(p for p in pending if id(p) not in taken)
        reordered = any(p.lane == LANE_CODE_BULK for p in self._pending)
        return batch, reordered

    def _scheduler(self) -> None:
        while True:
            qos_flush = False
            with self._cv:
                while not self._pending:
                    if self._stop.is_set():
                        return
                    self._cv.wait(0.1)
                # The deadline anchors on the OLDEST pending request: no
                # request waits longer than coalesce_us for company.
                deadline = (self._pending[0].received_at
                            + self.coalesce_us / 1e6)
                while (self._pending_sigs() < self.max_sigs
                       and not self._stop.is_set()):
                    limit = deadline
                    dl_ns = self._min_interactive_deadline_ns()
                    if dl_ns:
                        # Translate the epoch-ns interactive deadline onto
                        # the perf_counter timeline: flush guard_ns before
                        # it so verify+reply still fit inside the SLO.
                        qos_limit = (time.perf_counter()
                                     + (dl_ns - self.qos_guard_ns
                                        - time.time_ns()) / 1e9)
                        if qos_limit < limit:
                            limit = qos_limit
                    remaining = limit - time.perf_counter()
                    if remaining <= 0:
                        # Early only on the QoS clock? (coalesce window
                        # still open = a deadline-triggered flush.)
                        qos_flush = deadline - time.perf_counter() > 0
                        break
                    self._cv.wait(remaining)
                batch, _reordered = self._form_batch()
            if qos_flush:
                with self._lock:
                    self.qos_early_flushes += 1
            # Blocks while `depth` batches are in flight — backpressure
            # that keeps the executor at most one batch ahead. Timed so
            # shutdown can't wedge this thread if the executor exited
            # without releasing.
            while not self._slots.acquire(timeout=0.2):
                if self._stop.is_set():
                    return
            if self._stop.is_set():
                self._slots.release()
                return
            # Host half of the device dispatch runs HERE, on the scheduler
            # thread: while the executor holds the device with batch N,
            # this packs batch N+1's kernel arrays (limb decompression,
            # radix split, bucket padding) — the depth-2 slot already
            # admitted it. pack_device routes exactly like verify_batch
            # would (size/gate/scheme), returning None for batches the
            # verifier would host-route; the executor then takes the
            # ordinary unsplit path, so routing semantics never fork.
            jobs = [j for p in batch for j in p.jobs]
            packed = None
            pack_s = 0.0
            pack_fn = getattr(self.verifier, "pack_device", None)
            if pack_fn is not None and len(jobs) <= self.max_sigs:
                t_pack = time.perf_counter()
                try:
                    packed = pack_fn(jobs)
                except Exception:
                    packed = None  # unsplit path decides (and may reply ERR)
                pack_s = time.perf_counter() - t_pack
            self._exec_q.put((batch, jobs, packed, pack_s))

    # -- executor -----------------------------------------------------------

    def _executor(self) -> None:
        while True:
            item = self._exec_q.get()
            if item is _STOP:
                return
            batch, jobs, packed, pack_s = item
            t0 = time.perf_counter()
            err = None
            try:
                ok, tier = self._dispatch(jobs, packed)
            except Exception as exc:  # noqa: BLE001
                # Providers reject-never-raise, but a dying device backend
                # can still throw; an error REPLY (not silence) lets the
                # client degrade immediately instead of eating a deadline.
                ok, tier, err = None, 0, exc
            verify_s = time.perf_counter() - t0
            if _tm.ACTIVE is not None:
                _tm.inc("sidecar_batches_total")
                _tm.inc("sidecar_sigs_total", len(jobs))
                _tm.observe("sidecar_batch_sigs", len(jobs))
            with self._lock:
                self.batches += 1
                self.sigs += len(jobs)
                if len(batch) > 1:
                    self.cross_request_batches += 1
                if err is not None:
                    self.errors += 1
                self.verify_s_total += verify_s
                self.wait_s_total += sum(t0 - p.received_at for p in batch)
                if packed is not None:
                    self.packed_batches += 1
                    self.pack_s_total += pack_s
                if self.adaptive_coalesce:
                    self._adapt_observe(len(batch), len(jobs))
            offset = 0
            for p in batch:
                n = len(p.jobs)
                head = _VERIFY_REPLY_HDR.pack(
                    OP_VERIFY, p.req_id,
                    STATUS_OK if err is None else STATUS_ERR, tier,
                    t0 - p.received_at, verify_s)
                if err is None:
                    body = np.asarray(ok[offset:offset + n],
                                      bool).astype(np.uint8).tobytes()
                else:
                    body = repr(err).encode()[:512]
                offset += n
                try:
                    p.client.reply(head + body)
                except OSError:
                    pass  # client died mid-batch: its flows replay
            self._slots.release()

    def _device_batches(self) -> int:
        return getattr(self.verifier, "device_batches", 0) or 0

    def _dispatch(self, jobs, packed) -> tuple[np.ndarray, int]:
        """Verify one formed batch; returns (verdicts, tier), tier 1 when
        any of it ran on the device. A pre-packed batch dispatches as
        packed. Otherwise verify_batch runs in slices of at most max_sigs:
        one request larger than a bucket must not dispatch a bucket the
        warm-up never compiled (a cold compile there outlasts every client
        deadline). Each dispatch is recorded with its own size and tier."""
        if packed is not None:
            before = self._device_batches()
            ok = self.verifier.verify_packed(packed)
            tier = int(self._device_batches() > before)
            self._record_dispatch(len(jobs), tier, packed)
            return ok, tier
        parts, tier = [], 0
        for i in range(0, max(len(jobs), 1), self.max_sigs):
            part = jobs[i:i + self.max_sigs]
            before = self._device_batches()
            parts.append(self.verifier.verify_batch(part))
            on_device = int(self._device_batches() > before)
            self._record_dispatch(len(part), on_device, None)
            tier |= on_device
        ok = parts[0] if len(parts) == 1 else np.concatenate(parts)
        return ok, tier

    def _record_dispatch(self, n: int, tier: int, packed) -> None:
        """One dispatch of n signatures: its bucket and, on the device
        tier, its lanes. The packed handle knows the exact dispatched
        bucket and mesh width; the unsplit device path is reconstructed
        arithmetically (same ladder)."""
        b = bucket_for(n)
        with self._lock:
            self.batch_sigs_hist[b] = self.batch_sigs_hist.get(b, 0) + 1
            if not tier:
                return
            ndev = (packed.n_devices if packed is not None
                    else (self.mesh_devices or self.devices or 1))
            lanes = (packed.bucket if packed is not None
                     else pad_to_devices(b, ndev))
            real = len(packed.good) if packed is not None else n
            self.device_lanes += lanes
            self.pad_lanes += lanes - real
            share = lanes // ndev
            self.per_device_batch_sigs_hist[share] = (
                self.per_device_batch_sigs_hist.get(share, 0) + 1)

    # -- adaptive coalescing ------------------------------------------------

    def _adapt_observe(self, n_requests: int, n_sigs: int) -> None:
        """Retune coalesce_us from the observed batch fill — called under
        self._lock per executed batch when adaptive_coalesce is on. Every
        ADAPT_WINDOW batches: if batches fill to >= max_sigs/2 the deadline
        is pure added latency, shrink it multiplicatively; if they run
        below max_sigs/4 WHILE multiple requests are coalescing per batch
        (more company would actually arrive), grow it toward the ceiling so
        the mesh sees fuller buckets. The band between the thresholds is
        hysteresis — no change. Only the WINDOW LENGTH ever changes: the
        scheduler still anchors the deadline on the oldest pending request
        and still flushes early at max_sigs, so the p99 contract (no
        request waits more than coalesce_us for company) holds at the new
        value from the next batch on."""
        self._win_batches += 1
        self._win_requests += n_requests
        self._win_sigs += n_sigs
        if self._win_batches < ADAPT_WINDOW:
            return
        mean = self._win_sigs / self._win_batches
        coalescing = self._win_requests > self._win_batches
        self._win_batches = self._win_requests = self._win_sigs = 0
        cur = self.coalesce_us
        if mean >= self.max_sigs / 2:
            new = int(cur * ADAPT_SHRINK)
        elif mean < self.max_sigs / 4 and coalescing:
            new = min(ADAPT_CEILING_US,
                      max(ADAPT_SEED_US, int(cur * ADAPT_GROW)))
        else:
            return
        if new != cur:
            self.coalesce_us = new
            self.coalesce_adjustments += 1

    def reset_window(self) -> None:
        """Cross-candidate seam (the autotune controller calls this
        between back-to-back sweep candidates, and the runtime leg's
        revert guard calls it to undo a bad tune): restore the
        CONFIGURED coalesce window and zero the adaptation window, so
        the next candidate's first ADAPT_WINDOW batches are judged on
        its own traffic, not the previous candidate's adapted state.
        Cumulative stats (batches/sigs/coalesce_adjustments) survive —
        this resets the control state, not the audit trail."""
        with self._lock:
            self.coalesce_us = self.coalesce_us_initial
            self._win_batches = self._win_requests = self._win_sigs = 0

    # -- stats --------------------------------------------------------------

    def stats(self) -> dict:
        from ..ops import last_backend_if_loaded

        v = self.verifier
        gate = getattr(v, "device_gate", None)
        dev_b = getattr(v, "device_batches", None)
        host_b = getattr(v, "host_batches", None)
        occupancy = None
        if dev_b is not None and host_b is not None:
            total = dev_b + host_b
            occupancy = round(dev_b / total, 3) if total else 0.0
        with self._lock:
            hist = {str(k): self.batch_sigs_hist[k]
                    for k in sorted(self.batch_sigs_hist)}
            per_dev_hist = {str(k): self.per_device_batch_sigs_hist[k]
                            for k in sorted(self.per_device_batch_sigs_hist)}
            lanes, pad = self.device_lanes, self.pad_lanes
            return {
                "address": self.address,
                "verifier": getattr(v, "name", None),
                "kernel_backend": last_backend_if_loaded(),
                "requests": self.requests,
                "batches": self.batches,
                "sigs": self.sigs,
                "cross_request_batches": self.cross_request_batches,
                "errors": self.errors,
                "batch_sigs_hist": hist,
                "device_batches": dev_b,
                "host_batches": host_b,
                "device_min_sigs": getattr(v, "device_min_sigs", None),
                "device_ready": (gate.is_set() if gate is not None
                                 else None),
                "device_occupancy": occupancy,
                # Mesh ownership: configured width, the PROVEN mesh size
                # (None until the warm thread builds it), why the warm/mesh
                # failed, and the pad/occupancy attribution per dispatched
                # device lane. per_device_occupancy is the fraction of each
                # device's lane share carrying a real signature (identical
                # across devices — the batch axis shards equally).
                "devices": self.devices or None,
                "mesh_devices": self.mesh_devices,
                "warm_error": self.warm_error,
                "packed_batches": self.packed_batches,
                "pack_s_total": round(self.pack_s_total, 6),
                "device_lanes": lanes,
                "pad_lanes": pad,
                "pad_fraction": (round(pad / lanes, 4) if lanes else 0.0),
                "per_device_occupancy": (
                    round((lanes - pad) / lanes, 4) if lanes else 0.0),
                "per_device_batch_sigs_hist": per_dev_hist,
                "coalesce_us": self.coalesce_us,
                "coalesce_us_initial": self.coalesce_us_initial,
                "adaptive_coalesce": self.adaptive_coalesce,
                "coalesce_adjustments": self.coalesce_adjustments,
                "max_sigs": self.max_sigs,
                "depth": self.depth,
                "wait_s_total": round(self.wait_s_total, 6),
                "verify_s_total": round(self.verify_s_total, 6),
                # QoS deadline scheduler (OP_VERIFY_QOS clients).
                "qos_guard_us": self.qos_guard_ns // 1000,
                "qos_early_flushes": self.qos_early_flushes,
                "qos_interactive_requests": self.qos_interactive_requests,
                "qos_bulk_requests": self.qos_bulk_requests,
            }


def main(argv: Sequence[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description="corda_tpu verification sidecar: one device-owning "
                    "verify server per host")
    parser.add_argument("--socket", required=True,
                        help="unix socket path or host:port to listen on")
    parser.add_argument("--verifier", default="jax",
                        help="server-side provider (cpu | jax | jax-shadow "
                             "| jax-sharded)")
    parser.add_argument("--coalesce-us", type=int, default=2000,
                        help="max time the oldest request waits for "
                             "cross-client company")
    parser.add_argument("--max-sigs", type=int, default=4096,
                        help="flush a coalesced batch early at this many "
                             "signatures (bucket capacity)")
    parser.add_argument("--depth", type=int, default=2,
                        help="batches formed-or-in-flight (double buffer)")
    parser.add_argument("--device-min-sigs", type=int, default=None,
                        help="override the server verifier's size crossover")
    parser.add_argument("--devices", type=int, default=None,
                        help="own a JAX device mesh of this many local "
                             "devices (data-parallel sharded verify); 1 or "
                             "unset keeps the single-device tier")
    parser.add_argument("--adaptive-coalesce", action="store_true",
                        help="retune coalesce_us from the observed batch "
                             "fill (grow toward full buckets, shrink when "
                             "batches fill early)")
    parser.add_argument("--qos-guard-us", type=int, default=2000,
                        help="flush a coalescing batch this long before "
                             "the earliest interactive deadline "
                             "(OP_VERIFY_QOS clients)")
    args = parser.parse_args(argv)

    if args.verifier.startswith("jax"):
        from ..ops import enable_persistent_compile_cache

        enable_persistent_compile_cache()
    server = SidecarServer(
        args.socket, verifier_kind=args.verifier,
        coalesce_us=args.coalesce_us, max_sigs=args.max_sigs,
        depth=args.depth, device_min_sigs=args.device_min_sigs,
        devices=args.devices, adaptive_coalesce=args.adaptive_coalesce,
        qos_guard_us=args.qos_guard_us)
    server.start()
    # The driver's wait_up parses this banner, like the node's.
    print(f"sidecar up at {server.address}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
