"""Verification sidecar (crypto/sidecar.py + node/verify_client.py):
protocol parity vs the CPU oracle path, cross-client coalescing, deadline/
capacity flush, and the kill-sidecar degrade → cooldown re-probe →
exactly-once contract. Fast tier runs everything in-process over unix
sockets; the multi-node soak is @slow.
"""

import os
import shutil
import tempfile
import threading
import time

import numpy as np
import pytest

from corda_tpu.crypto import sidecar as sc
from corda_tpu.crypto.keys import KeyPair, SignatureError
from corda_tpu.crypto.provider import CpuVerifier, VerifyJob
from corda_tpu.crypto.sidecar import SidecarServer
from corda_tpu.flows.api import FlowLogic, VerifySigRequest, register_flow
from corda_tpu.node.config import BatchConfig, NodeConfig
from corda_tpu.node.node import Node
from corda_tpu.node.verify_client import (SidecarError, SidecarVerifier,
                                          fetch_sidecar_stats)


@pytest.fixture
def sock_path():
    # Short /tmp path on purpose: AF_UNIX paths cap at ~108 bytes and
    # pytest's tmp_path nests deep enough to blow it.
    d = tempfile.mkdtemp(prefix="sct-", dir="/tmp")
    try:
        yield os.path.join(d, "s.sock")
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _server(sock_path, **kw):
    kw.setdefault("verifier", CpuVerifier())
    kw.setdefault("coalesce_us", 0)
    return SidecarServer(sock_path, **kw).start()


def _garbage(n):
    return [VerifyJob(bytes(32), bytes(32), bytes(64))] * n


def _corpus():
    """Accept AND reject lanes plus the malformed/unknown-scheme edges."""
    kp = KeyPair.generate(b"\x07" * 32)
    msg = b"sidecar-parity".ljust(32, b".")
    sig = kp.sign(msg)
    pk, raw = bytes(sig.by.encoded), bytes(sig.bytes)
    bad = raw[:5] + bytes([raw[5] ^ 1]) + raw[6:]
    kp2 = KeyPair.generate(b"\x08" * 32)
    msg2 = b"second-signer-much-longer-message-" * 3
    sig2 = kp2.sign(msg2)
    return [
        VerifyJob(pk, msg, raw),                        # accept
        VerifyJob(pk, msg, bad),                        # reject
        VerifyJob(bytes(sig2.by.encoded), msg2, bytes(sig2.bytes)),
        VerifyJob(b"\x01" * 31, msg, raw),              # malformed pk
        VerifyJob(pk, msg, raw[:63]),                   # malformed sig
        VerifyJob(pk, msg, raw, scheme="nope"),         # unknown scheme
        VerifyJob(pk, msg2, raw),                       # wrong message
    ]


# ---------------------------------------------------------------------------
# Wire codec
# ---------------------------------------------------------------------------


def test_wire_roundtrip_variable_length_messages():
    jobs = [VerifyJob(bytes([i]) * 32, b"m" * (i * 7), bytes([i]) * 64)
            for i in range(1, 6)]
    req_id, decoded = sc.decode_verify_request(
        sc.encode_verify_request(42, jobs))
    assert req_id == 42
    assert [(j.pubkey, j.message, j.sig) for j in decoded] == \
           [(j.pubkey, j.message, j.sig) for j in jobs]


def test_bucket_ladder_matches_kernel():
    assert sc.bucket_for(1) == 64
    assert sc.bucket_for(80) == 256
    assert sc.bucket_for(4096) == 4096
    assert sc.bucket_for(10 ** 9) == 65536


# ---------------------------------------------------------------------------
# Protocol parity vs CpuVerifier
# ---------------------------------------------------------------------------


def test_protocol_parity_vs_cpu_verifier(sock_path):
    srv = _server(sock_path)
    try:
        jobs = _corpus()
        cli = SidecarVerifier(sock_path, device_min_sigs=0)
        out = cli.verify_batch(jobs)
        want = CpuVerifier().verify_batch(jobs)
        assert np.array_equal(out, want), (out.tolist(), want.tolist())
        # Everything routed through the sidecar, nothing fell back.
        assert cli.device_batches == 1
        assert cli.host_batches == 0
        assert cli.fallbacks == 0
        # Malformed + unknown-scheme jobs stayed local: only the four
        # well-formed ed25519 jobs rode the wire.
        assert cli.sidecar_sigs == 4
        stats = srv.stats()
        assert stats["requests"] == 1
        assert stats["sigs"] == 4
    finally:
        srv.stop()


def test_stats_and_ping_endpoints(sock_path):
    srv = _server(sock_path)
    try:
        cli = SidecarVerifier(sock_path, device_min_sigs=0)
        cli.warm()  # OP_PING round trip
        stats = fetch_sidecar_stats(sock_path)
        assert stats["verifier"] == "cpu-openssl"
        assert stats["batches"] == 0
        assert stats["coalesce_us"] == 0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Coalescing scheduler
# ---------------------------------------------------------------------------


def test_cross_client_requests_coalesce_into_one_bucket(sock_path):
    # A generous window so both clients land inside it; capacity (4096)
    # never reached, so exactly one deadline flush serves both.
    srv = _server(sock_path, coalesce_us=300_000)
    try:
        clients = [SidecarVerifier(sock_path, device_min_sigs=0)
                   for _ in range(2)]
        barrier = threading.Barrier(2)
        outs = [None, None]

        def go(i):
            barrier.wait()
            outs[i] = clients[i].verify_batch(_garbage(40))

        threads = [threading.Thread(target=go, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert all(o is not None and len(o) == 40 and not o.any()
                   for o in outs)
        stats = srv.stats()
        assert stats["requests"] == 2
        assert stats["batches"] == 1  # ONE device dispatch for both
        assert stats["cross_request_batches"] == 1
        assert stats["sigs"] == 80
        assert stats["batch_sigs_hist"] == {"256": 1}  # pick_bucket(80)
    finally:
        srv.stop()


def test_deadline_flush_bounds_a_lonely_request(sock_path):
    srv = _server(sock_path, coalesce_us=150_000)
    try:
        cli = SidecarVerifier(sock_path, device_min_sigs=0)
        t0 = time.perf_counter()
        out = cli.verify_batch(_garbage(4))
        elapsed = time.perf_counter() - t0
        assert len(out) == 4
        # Held for company up to the deadline, then flushed alone.
        assert 0.10 <= elapsed < 1.5, elapsed
        assert srv.stats()["batches"] == 1
        assert srv.stats()["cross_request_batches"] == 0
    finally:
        srv.stop()


def test_oversized_request_verifies_in_bucket_slices(sock_path):
    # One request larger than max_sigs must not dispatch a bucket the
    # warm-up never compiled: the server verifies it in max_sigs slices
    # and answers it whole, in order. Each slice is counted as the
    # dispatch it was, with its own bucket and tier: a short last slice
    # that routes to the host tier adds no device lanes.
    sizes = []

    class Recording(CpuVerifier):
        device_batches = 0

        def verify_batch(self, jobs):
            sizes.append(len(jobs))
            if len(jobs) >= 60:  # stands in for the size crossover
                self.device_batches += 1
            return super().verify_batch(jobs)

    jobs = [j for j in _corpus() if len(j.pubkey) == 32
            and len(j.sig) == 64 and j.scheme == "ed25519"]
    n = len(jobs) * 30
    want = CpuVerifier().verify_batch(jobs * 30)
    srv = _server(sock_path, verifier=Recording(), max_sigs=64)
    try:
        cli = SidecarVerifier(sock_path, device_min_sigs=0,
                              deadline_ms=10_000.0)
        out = cli.verify_batch(jobs * 30)
        assert out.tolist() == want.tolist()
        assert max(sizes) <= 64 and sum(sizes) == n
        stats = srv.stats()
        assert stats["batches"] == 1 and stats["sigs"] == n
        assert sizes == [64, 56]
        assert stats["batch_sigs_hist"] == {"64": 2}
        on_device = [64]  # the 56-signature slice took the host tier
        lanes = sum(sc.bucket_for(k) for k in on_device)
        assert stats["device_lanes"] == lanes
        assert stats["pad_lanes"] == lanes - sum(on_device)
        assert sum(stats["per_device_batch_sigs_hist"].values()) == len(
            on_device)
    finally:
        srv.stop()


class _BrokenWarmVerifier:
    """A jax-tier verifier whose device warm-up fails."""

    name = "jax-batch"
    device_min_sigs = 512
    device_gate = None

    def warm(self):
        raise RuntimeError("mosaic refused the kernel")


def _await(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.01)
    return cond()


def test_sidecar_warm_failure_on_accelerator_ends_the_process(
        monkeypatch, sock_path):
    import jax

    exits = []
    monkeypatch.setattr(os, "_exit", exits.append)  # instead of dying
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    srv = _server(sock_path, verifier=_BrokenWarmVerifier())
    try:
        assert _await(lambda: exits), "the warm failure did not exit"
        assert exits == [70]
        assert "mosaic refused" in srv.warm_error
    finally:
        srv.stop()


def test_node_warm_failure_on_accelerator_ends_the_process(monkeypatch):
    import types

    import jax

    exits = []
    monkeypatch.setattr(os, "_exit", exits.append)
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(platform="tpu")])
    node = types.SimpleNamespace(
        smm=types.SimpleNamespace(verifier=_BrokenWarmVerifier()),
        config=types.SimpleNamespace(name="Raft0"))
    Node._warm_verifier_maybe(node)
    node._warm_thread.join(timeout=10.0)
    assert exits == [70]


def test_capacity_flush_beats_the_deadline(sock_path):
    # The window is far longer than the client deadline: only the early
    # flush at bucket capacity can answer in time.
    srv = _server(sock_path, coalesce_us=30_000_000, max_sigs=64)
    try:
        cli = SidecarVerifier(sock_path, device_min_sigs=0,
                              deadline_ms=10_000.0)
        t0 = time.perf_counter()
        out = cli.verify_batch(_garbage(64))
        elapsed = time.perf_counter() - t0
        assert len(out) == 64
        assert elapsed < 5.0, elapsed
        assert srv.stats()["batches"] == 1
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Failure lanes: error reply, kill -> degrade -> re-probe
# ---------------------------------------------------------------------------


class _RaisingVerifier:
    name = "raising"

    def verify_batch(self, jobs):
        raise RuntimeError("device backend died")


def test_server_verifier_error_reply_falls_back_to_host(sock_path):
    srv = _server(sock_path, verifier=_RaisingVerifier())
    try:
        jobs = _corpus()
        cli = SidecarVerifier(sock_path, device_min_sigs=0)
        out = cli.verify_batch(jobs)
        # Infra fault never rejects: the host tier answered, correctly.
        assert np.array_equal(out, CpuVerifier().verify_batch(jobs))
        assert cli.fallbacks == 1
        assert cli.degraded == 1
        assert srv.stats()["errors"] == 1
    finally:
        srv.stop()


def test_kill_sidecar_degrades_then_cooldown_reprobe_reopens(sock_path):
    srv = _server(sock_path)
    jobs = _corpus()
    want = CpuVerifier().verify_batch(jobs)
    cli = SidecarVerifier(sock_path, device_min_sigs=0,
                          reprobe_cooldown_s=0.05)
    try:
        assert np.array_equal(cli.verify_batch(jobs), want)
        assert cli.device_batches == 1
        srv.stop()  # kill the sidecar

        out = cli.verify_batch(jobs)
        assert np.array_equal(out, want)  # host tier answered
        assert cli.fallbacks == 1
        assert cli.degraded == 1
        assert cli.host_batches >= 1
        assert cli.device_gate is not None and not cli.device_gate.is_set()

        # While the gate is closed, batches host-route WITHOUT retrying
        # the socket (no new fallbacks).
        assert np.array_equal(cli.verify_batch(jobs), want)
        assert cli.fallbacks == 1

        # Resurrect the server on the same path: the cooldown re-probe
        # round-trips a garbage batch and re-opens the gate.
        srv = _server(sock_path)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not cli.device_gate.is_set():
            time.sleep(0.02)
        assert cli.device_gate.is_set(), "re-probe never re-opened the gate"
        assert cli.reprobes_ok >= 1

        before = cli.device_batches
        assert np.array_equal(cli.verify_batch(jobs), want)
        assert cli.device_batches == before + 1  # sidecar tier again
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Node-level wiring: config, assembly, flows, kill mid-traffic
# ---------------------------------------------------------------------------


@register_flow
class SidecarSigFlow(FlowLogic):
    def __init__(self, pubkey: bytes, message: bytes, sig_bytes: bytes):
        self.pubkey = pubkey
        self.message = message
        self.sig_bytes = sig_bytes

    def call(self):
        yield VerifySigRequest(self.pubkey, self.message, self.sig_bytes,
                               description="SidecarSigFlow")
        return "verified"


def _sig_args(seed=b"\x07" * 32, message=b"sidecar-verify-me".ljust(32, b".")):
    kp = KeyPair.generate(seed)
    sig = kp.sign(message)
    return bytes(sig.by.encoded), bytes(message), bytes(sig.bytes)


def _make_node(tmp_path, name="SidecarNode", **batch_kw):
    return Node(NodeConfig(
        name=name,
        base_dir=tmp_path / name,
        network_map=tmp_path / "netmap.json",
        batch=BatchConfig(max_wait_ms=0.5, **batch_kw),
    )).start()


def _pump(node, predicate, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        node.run_once(timeout=0.01)
        if predicate():
            return
    raise AssertionError("node did not settle in time")


def test_batch_config_parses_sidecar_keys(tmp_path):
    cfg = NodeConfig.from_dict({
        "name": "N", "base_dir": str(tmp_path),
        "batch": {"sidecar": "/tmp/x.sock", "sidecar_deadline_ms": 750.0},
    })
    assert cfg.batch.sidecar == "/tmp/x.sock"
    assert cfg.batch.sidecar_deadline_ms == 750.0
    # Disabled path defaults: bit-identical config to before.
    cfg2 = NodeConfig.from_dict({"name": "N", "base_dir": str(tmp_path)})
    assert cfg2.batch.sidecar == ""
    assert cfg2.batch.sidecar_deadline_ms == 2000.0


def test_node_assembly_without_sidecar_is_unchanged(tmp_path, monkeypatch):
    monkeypatch.delenv("CORDA_TPU_SIDECAR", raising=False)
    node = _make_node(tmp_path)
    try:
        assert node.smm.verifier.name == "cpu-openssl"
    finally:
        node.stop()


def test_node_assembly_env_override_selects_sidecar(tmp_path, monkeypatch):
    monkeypatch.setenv("CORDA_TPU_SIDECAR", "/tmp/env-sidecar.sock")
    node = _make_node(tmp_path, name="EnvSidecarNode")
    try:
        assert node.smm.verifier.name == "sidecar"
        assert node.smm.verifier.address == "/tmp/env-sidecar.sock"
    finally:
        node.stop()


def test_node_flows_verify_through_sidecar_and_survive_kill(
        tmp_path, sock_path, monkeypatch):
    # min_sigs=1: even single-sig flow batches ship to the server — the
    # whole point of the sidecar is that MICRO-batches flow out.
    monkeypatch.setenv("CORDA_TPU_SIDECAR_MIN_SIGS", "1")
    srv = _server(sock_path)
    node = _make_node(tmp_path, sidecar=sock_path)
    try:
        verifier = node.smm.verifier
        assert verifier.name == "sidecar"
        pk, msg, sig = _sig_args()
        good = node.start_flow(SidecarSigFlow(pk, msg, sig))
        bad = node.start_flow(
            SidecarSigFlow(pk, msg, bytes([sig[0] ^ 1]) + sig[1:]))
        _pump(node, lambda: good.result.done and bad.result.done)
        assert good.result.result() == "verified"
        with pytest.raises(SignatureError):
            bad.result.result()
        assert verifier.device_batches >= 1  # the sidecar served them
        assert srv.stats()["sigs"] >= 2

        # Kill the sidecar mid-traffic: new flows must still complete,
        # exactly once each, with correct verdicts — via the host tier.
        srv.stop()
        good2 = node.start_flow(SidecarSigFlow(pk, msg, sig))
        bad2 = node.start_flow(
            SidecarSigFlow(pk, msg, bytes([sig[0] ^ 1]) + sig[1:]))
        _pump(node, lambda: good2.result.done and bad2.result.done)
        assert good2.result.result() == "verified"
        with pytest.raises(SignatureError):
            bad2.result.result()
        assert verifier.fallbacks >= 1
        assert verifier.degraded >= 1
        # Exactly-once: each flow finished one time (no dup delivery).
        assert node.smm.metrics.get("finished") == 4
    finally:
        node.stop()
        srv.stop()


def test_node_metrics_carry_sidecar_and_effective_min_sigs(
        tmp_path, sock_path, monkeypatch):
    from corda_tpu.node.rpc import NodeRpcOps

    monkeypatch.setenv("CORDA_TPU_SIDECAR_MIN_SIGS", "1")
    srv = _server(sock_path)
    node = _make_node(tmp_path, sidecar=sock_path)
    try:
        m = NodeRpcOps(node).node_metrics()
        assert m["verifier"] == "sidecar"
        assert m["sidecar"]["address"] == sock_path
        assert m["sidecar"]["min_sigs"] == 1
        # Satellite: the EFFECTIVE crossover is stamped (== the live value
        # when no adaptive adjustment has happened yet).
        assert m["verify_effective_min_sigs"] == 1
    finally:
        node.stop()
        srv.stop()

    # Sidecar-less node: same schema, sidecar None, effective falls back
    # to the verifier's device_min_sigs (None for cpu).
    monkeypatch.delenv("CORDA_TPU_SIDECAR", raising=False)
    node2 = _make_node(tmp_path, name="PlainNode")
    try:
        m2 = NodeRpcOps(node2).node_metrics()
        assert m2["sidecar"] is None
        assert "verify_effective_min_sigs" in m2
    finally:
        node2.stop()


def test_member_stamp_reports_occupancy_and_sidecar():
    from corda_tpu.tools.loadtest import _member_stamp

    stamp = _member_stamp({
        "verifier": "sidecar", "verify_device_batches": 3,
        "verify_host_batches": 1, "verify_effective_min_sigs": 16,
        "verify_static_min_sigs": 16,
        "sidecar": {"batches": 3, "fallbacks": 0},
    }, device="cpu")
    assert stamp["device_occupancy"] == 0.75
    assert stamp["effective_min_sigs"] == 16
    assert stamp["sidecar"] == {"batches": 3, "fallbacks": 0}
    # No batches at all -> occupancy is honestly unknown, not 0.
    empty = _member_stamp({}, device="cpu")
    assert empty["device_occupancy"] is None
    assert empty["sidecar"] is None


# ---------------------------------------------------------------------------
# Multi-node soak (@slow): the real multiprocess harness with --sidecar
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_multiprocess_loadtest_with_sidecar_commits_and_stamps():
    from corda_tpu.tools.loadtest import run_loadtest_multiprocess

    res = run_loadtest_multiprocess(
        n_tx=24, width=4, clients=1, notary="raft-validating",
        cluster_size=3, verifier="cpu", notary_device="cpu",
        sidecar=True, max_seconds=300.0)
    assert res.tx_committed == 24
    assert res.sidecar is not None and "error" not in res.sidecar
    assert res.sidecar["sigs"] > 0
    assert res.sidecar["requests"] > 0
    member_sidecars = [s.get("sidecar") for s in res.node_stamps.values()]
    assert any(s and s.get("batches", 0) > 0 for s in member_sidecars), (
        "no member shipped a batch to the sidecar")
    assert all(not (s or {}).get("fallbacks") for s in member_sidecars)
