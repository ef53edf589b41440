"""The cross-node tracing subsystem (corda_tpu/obs/).

Covers the ISSUE acceptance list: the stitched trace over the in-memory
network (one trace_id from the client flow through the responder notary
flow, correct span parentage), the raft commit-path spans over a real TCP
cluster, device-batch fan-in (one batch span carries every member flow's
trace id), the disarmed-path overhead guard (one attribute check, no span
allocation, no envelope growth), the merged Chrome trace + stage breakdown
collectors, and the satellite metrics-history / transport-stats surfaces.
"""

import json
import urllib.request
from collections import deque

import pytest

from corda_tpu.crypto.provider import CpuVerifier
from corda_tpu.flows.notary import NotaryClientFlow
from corda_tpu.node.config import NodeConfig
from corda_tpu.node.node import Node
from corda_tpu.obs import collect, trace as obs
from corda_tpu.testing import DummyContract
from corda_tpu.testing.mock_network import MockNetwork

import sys
import os
sys.path.insert(0, os.path.dirname(__file__))
from test_tcp_node import issue_and_move, pump_until  # noqa: E402


@pytest.fixture()
def recorder():
    rec = obs.arm("test", capacity=4096)
    yield rec
    obs.disarm()


@pytest.fixture()
def net():
    network = MockNetwork(verifier=CpuVerifier())
    yield network
    network.stop_nodes()


def _notarise_move(net):
    notary = net.create_notary_node("Notary")
    alice = net.create_node("Alice")
    bob = net.create_node("Bob")
    builder = DummyContract.generate_initial(
        alice.identity.ref(b"\x00"), 7, notary.identity)
    builder.sign_with(alice.key)
    issue_stx = builder.to_signed_transaction()
    alice.record_transaction(issue_stx)
    move = DummyContract.move(issue_stx.tx.out_ref(0),
                              bob.identity.owning_key)
    move.sign_with(alice.key)
    move_stx = move.to_signed_transaction(check_sufficient_signatures=False)
    handle = alice.start_flow(NotaryClientFlow(move_stx))
    net.run_network()
    assert handle.result.done and handle.result.exception() is None
    return handle


# ---------------------------------------------------------------------------
# Recorder unit behaviour
# ---------------------------------------------------------------------------


def test_ring_overwrites_oldest_and_counts_drops():
    rec = obs.SpanRecorder("n", capacity=4)
    for i in range(6):
        rec.record("s", float(i), float(i) + 0.5)
    snap = rec.snapshot()
    assert [s["t_start"] for s in snap] == [2.0, 3.0, 4.0, 5.0]
    stats = rec.stats()
    assert stats["recorded"] == 6
    assert stats["buffered"] == 4
    assert stats["dropped"] == 2


def test_link_map_is_bounded():
    rec = obs.SpanRecorder("n", capacity=4)
    for i in range(obs.LINK_MAP_MAX + 5):
        rec.register_link(i.to_bytes(8, "big"), b"t" * 8, b"s" * 8)
    # Wholesale clear at the cap: correlation loss beats unbounded growth.
    assert len(rec._links) <= obs.LINK_MAP_MAX


def test_arm_from_env_parses_capacity(monkeypatch):
    monkeypatch.setenv(obs.ENV_VAR, "128")
    try:
        rec = obs.arm_from_env("envnode")
        assert rec is not None and rec.capacity == 128
        monkeypatch.setenv(obs.ENV_VAR, "on")
        rec = obs.arm_from_env("envnode")
        assert rec is not None and rec.capacity == obs.DEFAULT_CAPACITY
        monkeypatch.setenv(obs.ENV_VAR, "nonsense")
        assert obs.arm_from_env("envnode") is None
    finally:
        obs.disarm()


# ---------------------------------------------------------------------------
# Stitched trace over the in-memory network
# ---------------------------------------------------------------------------


def test_inmem_notarise_stitches_one_trace(recorder, net):
    _notarise_move(net)
    spans = recorder.snapshot()
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)

    client = by_name["flow:NotaryClientFlow"]
    assert len(client) == 1
    root = client[0]
    assert root["parent"] is None
    trace_id = root["trace_id"]

    # The responder flow inherited the client's trace over Message.trace
    # and parents to the client's root span.
    service = [s for s in spans
               if s["name"] == "flow:ValidatingNotaryFlow"
               and s["trace_id"] == trace_id]
    assert len(service) == 1
    assert service[0]["parent"] == root["span_id"]

    # The notary-side processing span parents to the responder flow.
    proc = [s for s in by_name.get("notary_process", ())
            if s["trace_id"] == trace_id]
    assert len(proc) == 1
    assert proc[0]["parent"] == service[0]["span_id"]
    assert proc[0]["attrs"]["ok"] is True

    # Every recorded span for this transaction shares ONE trace id.
    tx_spans = [s for s in spans if s["trace_id"] == trace_id]
    assert len(tx_spans) >= 3
    # And the stages nest inside the root's wall time (small slack for the
    # epoch re-anchoring of perf-counter durations).
    for s in tx_spans:
        assert s["t_end"] <= root["t_end"] + 0.05


def test_stage_breakdown_from_inmem_trace(recorder, net):
    _notarise_move(net)
    snap = {"node": "inproc", "spans": recorder.snapshot()}
    breakdown = collect.stage_breakdown([snap])
    assert breakdown["traces"] >= 1
    assert set(breakdown["stages"]) == set(collect.STAGES)
    e2e = breakdown["end_to_end"]["mean_ms"]
    assert e2e > 0
    # The derived reply stage closes the attribution gap: stage sum tracks
    # end-to-end by construction.
    total = sum(v["mean_ms"] for v in breakdown["stages"].values())
    assert total <= e2e * 1.05


def test_merged_chrome_trace_shape(recorder, net, tmp_path):
    _notarise_move(net)
    path = tmp_path / "trace.json"
    collect.write_chrome_trace(str(path), [
        {"node": "inproc", "spans": recorder.snapshot()}])
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    names = {e["name"] for e in events if e["ph"] == "X"}
    assert "flow:NotaryClientFlow" in names
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in events)
    for e in events:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] > 0


# ---------------------------------------------------------------------------
# Raft commit-path spans over a real TCP cluster
# ---------------------------------------------------------------------------


def test_raft_cluster_commit_spans(recorder, tmp_path):
    cluster = ("RaftA", "RaftB", "RaftC")
    nodes = []
    for name in cluster:
        nodes.append(Node(NodeConfig(
            name=name, base_dir=tmp_path / name, notary="raft-simple",
            raft_cluster=cluster,
            network_map=tmp_path / "netmap.json")).start())
    alice = Node(NodeConfig(name="Alice", base_dir=tmp_path / "Alice",
                            network_map=tmp_path / "netmap.json")).start()
    everyone = nodes + [alice]
    try:
        import time as _time
        deadline = _time.monotonic() + 15.0
        leader = None
        while _time.monotonic() < deadline and leader is None:
            for n in everyone:
                n.run_once(timeout=0.005)
            leader = next((n for n in nodes
                           if n.raft_member.role == "leader"), None)
        assert leader is not None, "no leader elected"
        for n in everyone:
            n.refresh_netmap()

        stx = issue_and_move(alice, leader.identity, magic=1)
        h = alice.start_flow(NotaryClientFlow(stx))
        pump_until(everyone, lambda: h.result.done)
        assert h.result.exception() is None

        spans = recorder.snapshot()
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)

        roots = [s for s in by_name.get("flow:NotaryClientFlow", ())
                 if s["parent"] is None]
        assert len(roots) == 1
        trace_hex = roots[0]["trace_id"]

        # The per-transaction commit span from the flow's point of view.
        commits = [s for s in by_name.get("raft_commit", ())
                   if s["trace_id"] == trace_hex]
        assert len(commits) == 1 and commits[0]["attrs"]["ok"] is True

        # The batch-level consensus spans fan IN: member_traces carries
        # this transaction's trace id through append/fsync/replication.
        for stage in ("raft_append", "fsync", "replication"):
            attributed = [
                s for s in by_name.get(stage, ())
                if trace_hex in (s["attrs"].get("member_traces") or ())]
            assert attributed, f"no {stage} span attributed to the trace"
    finally:
        for n in everyone:
            n.stop()


# ---------------------------------------------------------------------------
# Device-batch fan-in from the feeder thread
# ---------------------------------------------------------------------------


def test_feeder_batch_spans_carry_member_traces(recorder):
    from corda_tpu.crypto.async_verify import AsyncVerifyService
    from corda_tpu.crypto.provider import VerifyJob

    class _OkVerifier:
        name = "stub-ok"

        def verify_batch(self, jobs):
            return [True] * len(jobs)

    class _Fsm:
        def __init__(self):
            self.trace_id = obs.new_trace_id()

    fsms = [_Fsm(), _Fsm()]
    svc = AsyncVerifyService(_OkVerifier(), depth=2, adaptive=False)
    jobs = [VerifyJob(pubkey=b"\x00" * 32, message=b"\x01" * 32,
                     sig=b"\x02" * 64) for _ in range(2)]
    try:
        svc.submit(jobs, [(fsm, None) for fsm in fsms])
        import time as _time
        deadline = _time.monotonic() + 10.0
        done = []
        while not done and _time.monotonic() < deadline:
            done = svc.drain()
            _time.sleep(0.002)
        assert done, "batch never completed"
    finally:
        svc.close()

    spans = {s["name"]: s for s in recorder.snapshot()}
    for stage in ("queue_wait", "device_verify"):
        assert stage in spans, f"missing {stage} span"
        members = spans[stage]["attrs"]["member_traces"]
        assert sorted(members) == sorted(f.trace_id.hex() for f in fsms)
        assert spans[stage]["attrs"]["sigs"] == 2


# ---------------------------------------------------------------------------
# Overhead guard: the disarmed path is one attribute check
# ---------------------------------------------------------------------------


def test_disarmed_path_allocates_nothing(net, monkeypatch):
    assert obs.ACTIVE is None

    def _boom(*a, **kw):  # any span/id allocation while disarmed is a bug
        raise AssertionError("tracing touched while disarmed")

    class _NoAnnotation:  # the profiler's annotation, no session running
        is_enabled = staticmethod(lambda: False)
        __init__ = _boom

    monkeypatch.setattr(obs, "new_trace_id", _boom)
    monkeypatch.setattr(obs, "new_span_id", _boom)
    monkeypatch.setattr(obs.SpanRecorder, "record", _boom)
    # The verify path's spans (provider._dispatch_mixed on every batch).
    monkeypatch.setattr(obs, "_LiveSpan", _boom)
    monkeypatch.setattr(obs, "_ANNOTATION", _NoAnnotation)
    _notarise_move(net)
    # No envelope growth either: every message crossed with trace=None.
    assert net.messaging_network.sent_messages
    assert all(m.message.trace is None
               for m in net.messaging_network.sent_messages)


def test_tcp_wire_tuple_width_gated_on_arming():
    from types import SimpleNamespace

    from corda_tpu.node.messaging.api import TopicSession
    from corda_tpu.node.messaging.tcp import TcpMessaging

    fake = SimpleNamespace(
        my_address=SimpleNamespace(host="127.0.0.1", port=12345))
    ts = TopicSession("t", 0)
    assert obs.ACTIVE is None
    assert len(TcpMessaging._wire_tuple(fake, ts, b"u" * 8, b"d")) == 7
    obs.arm("wire")
    try:
        obs.clear_context()
        # Armed but no context on this thread: still the 7-field frame.
        assert len(TcpMessaging._wire_tuple(fake, ts, b"u" * 8, b"d")) == 7
        obs.set_context(b"t" * 8, b"s" * 8)
        wide = TcpMessaging._wire_tuple(fake, ts, b"u" * 8, b"d")
        assert len(wide) == 9 and wide[7] == b"t" * 8 and wide[8] == b"s" * 8
    finally:
        obs.disarm()


# ---------------------------------------------------------------------------
# Satellites: metrics history deque + web surfaces + inmem transport stats
# ---------------------------------------------------------------------------


def test_metrics_history_is_bounded_deque_and_served(tmp_path):
    node = Node(NodeConfig(name="WebNode", base_dir=tmp_path / "WebNode",
                           network_map=tmp_path / "netmap.json",
                           web_port=0)).start()
    try:
        assert isinstance(node.metrics_history, deque)
        assert node.metrics_history.maxlen == Node.METRICS_HISTORY_KEEP
        for i in range(Node.METRICS_HISTORY_KEEP + 10):
            node.metrics_history.append({"t": i})
        assert len(node.metrics_history) == Node.METRICS_HISTORY_KEEP
        assert node.metrics_history[0] == {"t": 10}  # oldest self-trimmed

        base = f"http://127.0.0.1:{node.webserver.port}"
        with urllib.request.urlopen(f"{base}/api/metrics/history",
                                    timeout=5.0) as resp:
            history = json.load(resp)
        assert isinstance(history, list)
        assert len(history) == Node.METRICS_HISTORY_KEEP
        # Served newest-first: dashboards and flight-dump readers want the
        # most recent sample at index 0 (the deque itself stays
        # oldest-first append order).
        assert history[0] == {"t": Node.METRICS_HISTORY_KEEP + 9}
        assert history[-1] == {"t": 10}
    finally:
        node.stop()


def test_api_trace_serves_span_buffer(tmp_path):
    node = Node(NodeConfig(name="TraceNode", base_dir=tmp_path / "TraceNode",
                           network_map=tmp_path / "netmap.json",
                           web_port=0)).start()
    try:
        base = f"http://127.0.0.1:{node.webserver.port}"
        with urllib.request.urlopen(f"{base}/api/trace",
                                    timeout=5.0) as resp:
            disarmed = json.load(resp)
        assert disarmed == {"node": "TraceNode", "armed": False,
                            "spans": [], "stats": None}
        rec = obs.arm("TraceNode", capacity=16)
        try:
            rec.record("demo", 1.0, 2.0)
            with urllib.request.urlopen(f"{base}/api/trace",
                                        timeout=5.0) as resp:
                armed = json.load(resp)
        finally:
            obs.disarm()
        assert armed["armed"] is True
        assert [s["name"] for s in armed["spans"]] == ["demo"]
        assert armed["stats"]["recorded"] == 1
    finally:
        node.stop()


def test_inmem_transport_stats_schema_parity(net):
    from corda_tpu.node.messaging.tcp import TcpMessaging

    node = net.create_node("StatsNode")
    stats = node.messaging.transport_stats()
    expected = {
        "outbox_appends", "outbox_bursts", "outbox_burst_frames",
        "outbox_max_burst", "outbox_burst_avg", "bridge_flushes",
        "bridge_flush_frames", "bridge_max_flush", "bridge_flush_avg",
        "redeliveries", "stale_resends", "poison_pending", "poison_drops",
        "poison_retry_limit", "frames_sent_total",
    }
    assert set(stats) == expected
    assert stats["redeliveries"] == 0
    # Real parity, not just the inmem side of it: a TcpMessaging instance
    # (not started: no sockets, just counter state) must expose the exact
    # same key set, so cluster collectors can merge stats without
    # per-transport special cases.
    tcp_stats = TcpMessaging().transport_stats()
    assert set(tcp_stats) == set(stats) == expected
