"""Always-on telemetry plane (corda_tpu/obs/telemetry.py + export.py).

Covers the ISSUE acceptance list: the pre-interned metric registry (an
unregistered name raises instead of silently vanishing), power-of-two
histogram bucket math, the Prometheus text endpoint serving EVERY
registered metric in valid exposition form (node webserver GET /metrics
and the sidecar's OP_METRICS frame), exact cross-process snapshot
merging, the round profiler attributing >= 90% of live round wall time,
and the flight recorder's exactly-one-artifact-per-reason latch across
its trigger matrix (manual/SLO-breach, overload spike, crash).
"""

import json
import os
import shutil
import tempfile
import urllib.request

import pytest

from corda_tpu.crypto.provider import CpuVerifier, VerifyJob
from corda_tpu.crypto.sidecar import SidecarServer
from corda_tpu.node.config import NodeConfig
from corda_tpu.node.node import Node
from corda_tpu.node.verify_client import SidecarVerifier
from corda_tpu.obs import telemetry as tm
from corda_tpu.obs.export import (CONTENT_TYPE, PREFIX, collect_cluster,
                                  fetch_sidecar_metrics, merge_snapshots,
                                  parse_prometheus, render_prometheus)


@pytest.fixture()
def fresh():
    """A fresh registry for isolation; leaves a fresh one armed after
    (always-on is the module's default state, tests must restore it)."""
    reg = tm.arm()
    yield reg
    tm.arm()


# ---------------------------------------------------------------------------
# Registry: pre-interned names, rejection, disarmed cost
# ---------------------------------------------------------------------------


def test_registry_preinterns_every_registered_name(fresh):
    assert set(fresh.counters) == set(tm.COUNTER_NAMES)
    assert set(fresh.histograms) == set(tm.HISTOGRAM_NAMES)
    assert tm.METRIC_NAMES == (set(tm.COUNTER_NAMES)
                               | set(tm.HISTOGRAM_NAMES))


def test_unregistered_names_raise(fresh):
    with pytest.raises(ValueError, match="not registered"):
        fresh.counter("made_up_total")
    with pytest.raises(ValueError, match="not registered"):
        fresh.histogram("made_up_seconds")
    with pytest.raises(ValueError):
        tm.inc("made_up_total")
    with pytest.raises(ValueError):
        tm.observe("made_up_seconds", 0.1)


def test_helpers_update_the_active_registry(fresh):
    tm.inc("rounds_total")
    tm.inc("verify_sigs_total", 5)
    tm.observe("verify_batch_sigs", 5)
    snap = tm.snapshot()
    assert snap["counters"]["rounds_total"] == 1
    assert snap["counters"]["verify_sigs_total"] == 5
    assert snap["histograms"]["verify_batch_sigs"]["count"] == 1


def test_disarmed_path_is_a_noop_even_for_bad_names():
    # The hot-path guard is the attribute check — while disarmed nothing
    # validates, allocates, or raises (the one-attribute-check cost bound).
    tm.disarm()
    try:
        tm.inc("not_even_registered")
        tm.observe("also_not_registered", 1.0)
        tm.observe_round(0.01, {"poll": 0.01})
        assert tm.snapshot() is None
        assert tm.flight_trigger("crash") is None
    finally:
        tm.arm()


def test_observe_round_fans_into_phase_counters(fresh):
    tm.observe_round(0.010, {"poll": 0.006, "verify_wait": 0.002,
                             "apply": 0.001, "reply": 0.001})
    c = tm.snapshot()["counters"]
    assert c["rounds_total"] == 1
    assert c["round_wall_seconds_total"] == pytest.approx(0.010)
    assert c["round_phase_poll_seconds_total"] == pytest.approx(0.006)
    # Unnamed phases observe 0 — every phase histogram stays in lockstep.
    assert tm.snapshot()["histograms"][
        "round_phase_seal_seconds"]["count"] == 1


# ---------------------------------------------------------------------------
# Histogram bucket math
# ---------------------------------------------------------------------------


def test_power_of_two_buckets_for_counts():
    h = tm.Histogram("verify_batch_sigs")
    assert h.scale == 1
    for v in (1, 3, 4, 100):
        h.observe(v)
    # bucket i holds values with int(v).bit_length() == i.
    assert h.buckets == {1: 1, 2: 1, 3: 1, 7: 1}
    assert h.count == 4 and h.sum == 108
    assert h.bucket_upper(7) == 128


def test_seconds_histograms_scale_to_microseconds():
    h = tm.Histogram("round_wall_seconds")
    assert h.scale == 1_000_000
    h.observe(0.001)  # 1000 us -> bit_length 10
    assert h.buckets == {10: 1}
    assert h.bucket_upper(10) == pytest.approx(1024 / 1e6)


def test_huge_values_clamp_into_the_top_bucket():
    h = tm.Histogram("round_wall_seconds")
    h.observe(1e30)
    assert h.buckets == {63: 1}


def test_quantile_overestimates_by_at_most_one_bucket():
    h = tm.Histogram("verify_batch_sigs")
    for v in (10, 10, 10, 1000):
        h.observe(v)
    assert h.quantile(0.5) == 16       # 10 lives in (8, 16]
    assert h.quantile(1.0) == 1024
    assert tm.Histogram("verify_batch_sigs").quantile(0.5) is None


# ---------------------------------------------------------------------------
# format_breakdown
# ---------------------------------------------------------------------------


def test_format_breakdown_shares_coverage_busiest():
    rp = {"poll": 0.6, "verify_wait": 0.2, "seal": 0.0, "replicate": 0.05,
          "apply": 0.05, "reply": 0.05, "wall": 1.0, "rounds": 10}
    bd = tm.format_breakdown(rp)
    assert bd["rounds"] == 10 and bd["wall_s"] == 1.0
    assert bd["phases"]["poll"]["share"] == pytest.approx(0.6)
    assert bd["coverage"] == pytest.approx(0.95)
    assert bd["busiest_phase"] == "poll"


def test_format_breakdown_abstains_without_rounds():
    assert tm.format_breakdown(None) is None
    assert tm.format_breakdown({}) is None
    assert tm.format_breakdown({"rounds": 0, "wall": 0.0}) is None


def test_loadtest_busiest_stage_is_guarded():
    from corda_tpu.tools.loadtest import (BUSIEST_STAGE_MIN_ROUNDS,
                                          _busiest_stage)

    few = {"pump": 9.0, "fsync": 1.0, "rounds": BUSIEST_STAGE_MIN_ROUNDS - 1}
    assert _busiest_stage(few) is None       # abstains under-sampled
    assert _busiest_stage(None) is None
    enough = dict(few, rounds=500)
    # "rounds" is an integer count riding in the seconds dict — it must
    # never be crowned the busiest stage.
    assert _busiest_stage(enough) == "pump"
    tied = {"verify": 2.0, "fsync": 2.0, "rounds": 100}
    assert _busiest_stage(tied) == "fsync"   # deterministic: alphabetical
    # A delta window that did no measured work abstains too — crowning
    # the alphabetical first of all-zero stages is a fabricated verdict.
    assert _busiest_stage({"pump": 0.0, "fsync": 0.0, "rounds": 100}) is None


def test_format_breakdown_overlap_rides_beside_phases_no_double_count():
    """Pipelined commit plane: executor apply time is reported in its own
    ``overlap`` block, NEVER inside ``phases`` — coverage stays a
    partition of the consensus thread's wall time, so overlap can push
    attributed work past 100% of wall without corrupting the >= 0.9
    acceptance bound."""
    rp = {"poll": 0.5, "verify_wait": 0.1, "seal": 0.1, "replicate": 0.1,
          "apply": 0.1, "reply": 0.05, "wall": 1.0, "rounds": 30,
          "overlap_apply": 0.4}
    bd = tm.format_breakdown(rp)
    assert bd["coverage"] == pytest.approx(0.95)  # six phases only
    assert "overlap_apply" not in bd["phases"]
    assert set(bd["phases"]) == set(tm.ROUND_PHASES)
    assert bd["overlap"]["apply"]["total_s"] == pytest.approx(0.4)
    assert bd["overlap"]["apply"]["vs_wall"] == pytest.approx(0.4)
    # No double count: phase totals + overlap partition DIFFERENT threads'
    # time; the in-loop phase sum alone must stay <= wall.
    phase_sum = sum(p["total_s"] for p in bd["phases"].values())
    assert phase_sum <= bd["wall_s"] + 1e-9
    # The block is absent (not zeroed) when the plane never overlapped.
    serial = {k: v for k, v in rp.items() if k != "overlap_apply"}
    assert "overlap" not in tm.format_breakdown(serial)


# ---------------------------------------------------------------------------
# Prometheus render / parse / merge
# ---------------------------------------------------------------------------


def test_render_parse_round_trip_covers_every_metric(fresh):
    tm.inc("rounds_total", 3)
    tm.inc("verify_sigs_total", 7)
    tm.observe("verify_batch_sigs", 7)
    tm.observe("round_wall_seconds", 0.004)
    text = render_prometheus()
    parsed = parse_prometheus(text)
    # Every registered metric is served, including never-fired zeros.
    assert set(parsed["counters"]) == set(tm.COUNTER_NAMES)
    assert set(parsed["histograms"]) == set(tm.HISTOGRAM_NAMES)
    snap = tm.snapshot()
    for name, v in snap["counters"].items():
        assert parsed["counters"][name] == pytest.approx(v)
    h = parsed["histograms"]["verify_batch_sigs"]
    assert h["count"] == 1 and h["sum"] == pytest.approx(7.0)
    # Cumulative buckets end at +Inf == count.
    assert h["buckets"][-1] == (float("inf"), 1)


def test_render_accepts_a_snapshot_dict(fresh):
    tm.inc("rounds_total")
    assert (render_prometheus(tm.snapshot())
            == render_prometheus(fresh))


def test_parse_rejects_malformed_expositions():
    with pytest.raises(ValueError):
        parse_prometheus(f"{PREFIX}rounds_total garbage\n")
    with pytest.raises(ValueError):
        parse_prometheus("unprefixed_metric 1\n")
    with pytest.raises(ValueError):  # histogram without +Inf
        parse_prometheus(
            f"# TYPE {PREFIX}h histogram\n"
            f'{PREFIX}h_bucket{{le="1"}} 1\n'
            f"{PREFIX}h_sum 1\n{PREFIX}h_count 1\n")


def test_merge_snapshots_is_exact(fresh):
    a, b = tm.TelemetryRegistry(), tm.TelemetryRegistry()
    a.counter("verify_sigs_total").add(10)
    b.counter("verify_sigs_total").add(5)
    a.histogram("verify_batch_sigs").observe(3)   # bucket 2
    b.histogram("verify_batch_sigs").observe(3)   # same bucket: must sum
    b.histogram("verify_batch_sigs").observe(100)  # bucket 7
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    assert merged["counters"]["verify_sigs_total"] == 15
    h = merged["histograms"]["verify_batch_sigs"]
    assert h["count"] == 3 and h["sum"] == pytest.approx(106.0)
    assert h["buckets"] == {"2": 2, "7": 1}


def test_collect_cluster_reports_missing_nodes(fresh):
    tm.inc("rounds_total", 2)
    snap = tm.snapshot()
    out = collect_cluster({"A": snap, "B": None, "C": snap})
    assert out["missing"] == ["B"]
    assert set(out["nodes"]) == {"A", "C"}
    assert out["merged"]["counters"]["rounds_total"] == 4


def test_collect_cluster_zero_sample_node_merges_exactly(fresh):
    """A node that served a snapshot but never observed anything (all
    counters 0, no histogram samples) is PRESENT — not missing — and its
    zeros must not perturb the fold (the doctor reads merged counters;
    an idle member silently dropped would skew per-node ratios)."""
    busy, idle = tm.TelemetryRegistry(), tm.TelemetryRegistry()
    busy.counter("verify_sigs_total").add(7)
    busy.histogram("verify_batch_sigs").observe(5)
    out = collect_cluster({"busy": busy.snapshot(),
                           "idle": idle.snapshot()})
    assert out["missing"] == []
    assert set(out["nodes"]) == {"busy", "idle"}
    assert out["merged"]["counters"]["verify_sigs_total"] == 7
    h = out["merged"]["histograms"]["verify_batch_sigs"]
    assert h["count"] == 1 and h["buckets"] == {"3": 1}
    # And the merged view still renders/parses as valid exposition.
    parsed = parse_prometheus(render_prometheus(out["merged"]))
    assert parsed["counters"]["verify_sigs_total"] == 7


def test_merge_tolerates_stale_snapshot_schema(fresh):
    """A stale snapshot — captured by an older build that knew fewer
    metrics (keys absent entirely) and whose histogram block predates
    some fields — merges without KeyError: absent counters contribute 0,
    absent histogram fields default, and the newer node's series all
    survive. This is the rolling-upgrade shape collect_cluster meets."""
    new = tm.TelemetryRegistry()
    new.counter("doctor_runs_total").add(3)
    new.counter("rounds_total").add(10)
    new.histogram("round_wall_seconds").observe(0.25)
    stale = {"counters": {"rounds_total": 4.0},
             # Old shape: no scale, no sum, sparse buckets only.
             "histograms": {"round_wall_seconds": {"count": 2,
                                                   "buckets": {"17": 2}}}}
    merged = merge_snapshots([stale, new.snapshot()])
    assert merged["counters"]["rounds_total"] == 14
    assert merged["counters"]["doctor_runs_total"] == 3
    h = merged["histograms"]["round_wall_seconds"]
    assert h["count"] == 3
    # 0.25 s at the _seconds scale (1e6) lands in bucket 2^18; the stale
    # block's bucket 17 survives beside it with its own count.
    assert h["buckets"] == {"17": 2, "18": 1}
    # The merged histogram still renders as monotonic exposition.
    parse_prometheus(render_prometheus(merged))


def test_merge_disjoint_sparse_buckets_is_exact(fresh):
    """Two nodes whose sparse histograms share NO bucket index merge by
    union — every index survives with its own count, ordered, and the
    cumulative exposition stays monotonic (the power-of-two indices
    align across processes by construction, so this is exact)."""
    a, b = tm.TelemetryRegistry(), tm.TelemetryRegistry()
    a.histogram("verify_batch_sigs").observe(2)     # bucket idx 2
    a.histogram("verify_batch_sigs").observe(2)
    b.histogram("verify_batch_sigs").observe(1000)  # bucket idx 10
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    h = merged["histograms"]["verify_batch_sigs"]
    assert h["buckets"] == {"2": 2, "10": 1}
    assert list(h["buckets"]) == ["2", "10"]  # index-sorted
    assert h["count"] == 3 and h["sum"] == pytest.approx(1004.0)
    parsed = parse_prometheus(render_prometheus(merged))
    cums = [c for _, c in
            parsed["histograms"]["verify_batch_sigs"]["buckets"]]
    assert cums == [2, 3, 3]  # cumulative across the disjoint union


# ---------------------------------------------------------------------------
# Flight recorder: ring, deltas, and the exactly-one-artifact latch
# ---------------------------------------------------------------------------


def test_flight_latches_one_artifact_per_reason(tmp_path, fresh):
    rec = tm.FlightRecorder(str(tmp_path), node="t")
    rec.tick({"sheds": 1, "rate": "ignored-non-numeric"})
    rec.tick({"sheds": 4})
    rec.note("probe", detail="window context")
    p1 = rec.trigger("slo_breach", extra={"rate_tx_s": 480},
                     spans=[{"name": "qos_flush"}])
    p2 = rec.trigger("slo_breach", extra={"rate_tx_s": 960})
    assert p1 == p2 and os.path.exists(p1)
    art = json.loads(open(p1).read())
    assert art["reason"] == "slo_breach"
    assert art["extra"] == {"rate_tx_s": 480}  # first trigger wins
    assert art["spans"] == [{"name": "qos_flush"}]
    # The window carries per-tick DELTAS, not lifetime totals.
    assert art["window"][1]["delta"] == {"sheds": 3}
    assert art["window"][2]["kind"] == "probe"
    # A different reason is a different artifact; the registry counts it.
    p3 = rec.trigger("crash")
    assert p3 != p1 and os.path.exists(p3)
    assert sorted(rec.dumped) == ["crash", "slo_breach"]
    assert tm.snapshot()["counters"]["flight_dumps_total"] == 2


def test_flight_trigger_never_raises(tmp_path, fresh):
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("occupied")
    rec = tm.FlightRecorder(str(blocker / "sub"), node="t")
    assert rec.trigger("crash") is None  # unwritable dir: swallowed
    # Latched even on failure — a broken disk doesn't retry per crash.
    assert "crash" in rec.dumped


def test_ensure_flight_reads_env_and_is_idempotent(tmp_path, fresh,
                                                   monkeypatch):
    monkeypatch.delenv(tm.FLIGHT_ENV, raising=False)
    assert tm.ensure_flight() is None  # no dir anywhere: stays a no-op
    monkeypatch.setenv(tm.FLIGHT_ENV, str(tmp_path))
    fl = tm.ensure_flight(node="envnode")
    assert fl is fresh.flight and fl.node == "envnode"
    assert tm.ensure_flight(node="other") is fl  # idempotent
    path = tm.flight_trigger("fsck_failure", extra={"corrupt": 1})
    assert path is not None and os.path.exists(path)
    assert fl.stats()["dumped"] == {"fsck_failure": path}


# ---------------------------------------------------------------------------
# Trigger matrix: overload spike (admission) and crash (run loop)
# ---------------------------------------------------------------------------


def test_admission_overload_spike_dumps_once(tmp_path, fresh):
    from corda_tpu.qos.admission import SPIKE_SHEDS, AdmissionController
    from corda_tpu.qos.context import LANE_BULK

    fresh.flight = tm.FlightRecorder(str(tmp_path), node="adm")
    # One burst token, effectively no refill: everything after the first
    # request sheds.
    ac = AdmissionController(bulk_rate=1e-6, bulk_burst=1.0)
    sheds = 0
    for _ in range(SPIKE_SHEDS + 25):
        if ac.admit(LANE_BULK) is not None:
            sheds += 1
    assert sheds >= SPIKE_SHEDS
    assert list(fresh.flight.dumped) == ["overload_spike"]
    art = json.loads(open(fresh.flight.dumped["overload_spike"]).read())
    assert art["extra"]["sheds_in_window"] == SPIKE_SHEDS
    # The metric snapshot is captured AT the spike (the 50th shed), not
    # after the loop finished shedding.
    assert art["metrics"]["counters"]["admission_shed_total"] == SPIKE_SHEDS


def test_run_once_crash_dumps_and_reraises(tmp_path, fresh):
    tm.ensure_flight(str(tmp_path), node="crashnode")
    node = Node(NodeConfig(name="CrashNode",
                           base_dir=tmp_path / "CrashNode",
                           network_map=tmp_path / "netmap.json")).start()
    try:
        node.run_once(timeout=0.001)  # healthy round first

        def _boom():
            raise RuntimeError("injected round failure")

        node.smm.poll_services = _boom
        with pytest.raises(RuntimeError, match="injected"):
            node.run_once(timeout=0.001)
    finally:
        node.stop()
    assert list(fresh.flight.dumped) == ["crash"]
    art = json.loads(open(fresh.flight.dumped["crash"]).read())
    assert art["extra"]["node"] == "CrashNode"
    assert "RuntimeError: injected round failure" in art["extra"]["error"]


# ---------------------------------------------------------------------------
# Live round profiler + the node's /metrics surface
# ---------------------------------------------------------------------------


def test_live_rounds_attribute_90pct_and_metrics_endpoint(tmp_path, fresh):
    node = Node(NodeConfig(name="TmNode", base_dir=tmp_path / "TmNode",
                           network_map=tmp_path / "netmap.json",
                           web_port=0)).start()
    try:
        for _ in range(50):
            node.run_once(timeout=0.002)
        bd = tm.format_breakdown(node.smm.metrics["round_phase_s"])
        assert bd["rounds"] == 50
        # The acceptance bound: named phases attribute >= 90% of measured
        # round wall time (live measurement sits ~99.9%).
        assert bd["coverage"] >= 0.9
        assert bd["busiest_phase"] in tm.ROUND_PHASES
        # The registry saw the same rounds through observe_round.
        c = tm.snapshot()["counters"]
        assert c["rounds_total"] == 50
        assert c["round_wall_seconds_total"] == pytest.approx(
            node.smm.metrics["round_phase_s"]["wall"], rel=1e-6)

        base = f"http://127.0.0.1:{node.webserver.port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=5.0) as resp:
            assert resp.headers["Content-Type"] == CONTENT_TYPE
            parsed = parse_prometheus(resp.read().decode())
        assert set(parsed["counters"]) == set(tm.COUNTER_NAMES)
        assert set(parsed["histograms"]) == set(tm.HISTOGRAM_NAMES)
        assert parsed["counters"]["rounds_total"] >= 50
    finally:
        node.stop()


def test_node_metrics_rpc_carries_round_breakdown(tmp_path, fresh):
    from corda_tpu.node.rpc import NodeRpcOps

    node = Node(NodeConfig(name="RbNode", base_dir=tmp_path / "RbNode",
                           network_map=tmp_path / "netmap.json")).start()
    try:
        for _ in range(25):
            node.run_once(timeout=0.002)
        ops = NodeRpcOps(node)
        nm = ops.node_metrics()
        assert nm["round_breakdown"]["rounds"] == 25
        assert nm["round_breakdown"]["coverage"] >= 0.9
        assert nm["telemetry"]["rounds_total"] == 25
        ts = ops.telemetry_snapshot()
        assert ts["node"] == "RbNode" and ts["armed"] is True
        assert set(ts["snapshot"]["histograms"]) == set(tm.HISTOGRAM_NAMES)
    finally:
        node.stop()


def test_pipelined_live_rounds_attribute_90pct_with_overlap(tmp_path, fresh):
    """The >= 90%-attribution acceptance bound extends to the PIPELINED
    round loop: a raft leader whose apply runs on the detached executor
    still attributes >= 90% of consensus-thread wall time across the six
    phases, while the executor's apply seconds surface in the ``overlap``
    block BESIDE them — counted once, never inside coverage."""
    import time as _t

    from corda_tpu.contracts.structures import StateRef
    from corda_tpu.crypto.hashes import SecureHash
    from corda_tpu.crypto.keys import KeyPair
    from corda_tpu.crypto.party import Party
    from corda_tpu.node.services.raft import PutAllCommand

    node = Node(NodeConfig(name="PipeNode", base_dir=tmp_path / "PipeNode",
                           notary="raft-simple", raft_cluster=("PipeNode",),
                           network_map=tmp_path / "netmap.json")).start()
    try:
        deadline = _t.monotonic() + 15.0
        member = node.raft_member
        while member.role != "leader":
            node.run_once(timeout=0.002)
            assert _t.monotonic() < deadline, "no leader"
        assert member.config.pipeline is True
        party = Party("Client",
                      KeyPair.generate(b"\x01" * 32).public.composite)
        i = 0
        # Drive committed work through the loop until some executor apply
        # wall time lands inside a measured round window.
        while node.smm.metrics["round_phase_s"].get(
                "overlap_apply", 0.0) <= 0.0:
            member.submit(PutAllCommand(
                (StateRef(SecureHash.sha256(b"s%d" % i), 0),),
                SecureHash.sha256(b"t%d" % i), party, b"r%d" % i))
            node.run_once(timeout=0.002)
            i += 1
            assert _t.monotonic() < deadline, "no overlap observed"
        for _ in range(20):  # a healthy tail of ordinary rounds
            node.run_once(timeout=0.002)
        member.quiesce_apply()
        rp = node.smm.metrics["round_phase_s"]
        bd = tm.format_breakdown(rp)
        assert bd["coverage"] >= 0.9
        assert bd["overlap"]["apply"]["total_s"] > 0.0
        assert "overlap_apply" not in bd["phases"]  # no double count
        # The phases partition the wall: compared unrounded, since
        # format_breakdown rounds each total and the wall to 1e-6 apart.
        assert sum(rp[p] for p in bd["phases"]) <= rp["wall"] + 1e-9
        c = tm.snapshot()["counters"]
        assert c["round_overlap_apply_seconds_total"] > 0.0
        assert c["raft_apply_batches_total"] >= 1
        stamp = member.stamp()
        assert stamp["pipeline"] is True
        assert stamp["apply_batches"] >= 1
        assert stamp["overlap_s"]["apply"] > 0.0
    finally:
        node.stop()


# ---------------------------------------------------------------------------
# Sidecar OP_METRICS
# ---------------------------------------------------------------------------


@pytest.fixture()
def sock_path():
    # Short /tmp path on purpose: AF_UNIX paths cap at ~108 bytes.
    d = tempfile.mkdtemp(prefix="tmx-", dir="/tmp")
    try:
        yield os.path.join(d, "s.sock")
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_sidecar_serves_prometheus_over_op_metrics(sock_path, fresh):
    srv = SidecarServer(sock_path, verifier=CpuVerifier(),
                        coalesce_us=0).start()
    try:
        cli = SidecarVerifier(sock_path, device_min_sigs=0)
        cli.verify_batch([VerifyJob(bytes(32), bytes(32), bytes(64))] * 3)
        text = fetch_sidecar_metrics(sock_path)
        parsed = parse_prometheus(text)
        assert set(parsed["counters"]) == set(tm.COUNTER_NAMES)
        assert parsed["counters"]["sidecar_requests_total"] >= 1
        assert parsed["counters"]["sidecar_sigs_total"] >= 3
        h = parsed["histograms"]["sidecar_batch_sigs"]
        assert h["count"] >= 1
    finally:
        srv.stop()
