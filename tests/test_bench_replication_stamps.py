"""Guard: the raft bench section emits the commit-pipeline stamps on the
one-line JSON contract.

CPU smoke for the driver-facing shape only: the multiprocess sweep itself is
replaced, but the stamps it would gather are built by a REAL in-process
group commit (single-member RaftMember: quorum of one) flowing through the
REAL `_member_stamp` and `bench_raft_open_loop` — so a renamed or dropped
stamp field breaks here, not in a 10-minute bench run on the driver."""

import json
import os
import sys
import types

import bench
from corda_tpu.crypto.hashes import SecureHash
from corda_tpu.node.messaging.tcp import _Outbox
from corda_tpu.tools import loadtest
from corda_tpu.tools.loadtest import SweepResult, _member_stamp

sys.path.insert(0, os.path.dirname(__file__))
from test_bench_report import _stub_phases  # noqa: E402
from test_raft_group_commit import Net, cmd, elect, make_member  # noqa: E402

# Captured before any monkeypatching: the guard below needs the REAL
# function after _stub_phases replaces the module attribute.
_REAL_RAFT_OPEN_LOOP = bench.bench_raft_open_loop


def _real_group_commit_stamp(tmp_path) -> dict:
    """Drive the actual commit pipeline once and return its raft stamp."""
    net, t = Net(), [0.0]
    member = make_member(tmp_path, net, "Raft0", {}, lambda: t[0])
    elect(net, member, t)
    for i in range(3):
        member.submit(cmd(b"s%d" % i, b"t%d" % i, b"r%d" % i))
    member.flush_appends()
    member.quiesce_apply()  # pipelined plane: fold executor results back
    assert all(member.decided[b"r%d" % i].ok for i in range(3))
    return member.stamp()


def _burst_transport_stats() -> dict:
    """transport_stats() shape, fed by a real outbox burst."""
    outbox = _Outbox()
    outbox.append_many("peer", [(b"u1", b"f1"), (b"u2", b"f2")])
    s = outbox.stats
    return {"outbox_appends": s["appends"], "outbox_bursts": s["bursts"],
            "outbox_burst_frames": s["burst_frames"],
            "outbox_max_burst": s["max_burst"],
            "outbox_burst_avg": round(s["burst_frames"] / s["bursts"], 3),
            "bridge_flushes": 0, "bridge_flush_frames": 0,
            "bridge_max_flush": 0, "bridge_flush_avg": None}


def test_raft_bench_section_emits_replication_stamps(tmp_path, monkeypatch,
                                                     capsys):
    _stub_phases(monkeypatch)
    # _stub_phases stubs bench_raft_open_loop for the report-shape tests;
    # THIS guard exists to drive the real one (over a faked sweep), so put
    # it back.
    monkeypatch.setattr(bench, "bench_raft_open_loop", _REAL_RAFT_OPEN_LOOP)

    metrics = {"verifier": "cpu",
               "raft": _real_group_commit_stamp(tmp_path),
               "transport": _burst_transport_stats()}

    def fake_sweep(rates=(60.0, 240.0, 720.0, 1800.0), n_tx=250, **kw):
        result = types.SimpleNamespace(p50_ms=5.0, p90_ms=9.0, p99_ms=20.0,
                                       tx_per_sec=30.0, committed=n_tx)
        return SweepResult(results={r: result for r in rates},
                           node_stamps={"Raft0": _member_stamp(metrics,
                                                               "cpu")})

    monkeypatch.setattr(loadtest, "run_latency_sweep", fake_sweep)

    assert bench.main() == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1  # the single-line contract survives the new keys
    report = json.loads(out[0])
    section = report["baseline_configs"]["raft_open_loop_latency"]

    # The aggregated summary names the member and carries the new stamps.
    replication = section["replication"]
    assert replication["member"] == "Raft0"
    assert replication["role"] == "leader"
    assert replication["group_commit"] is True
    assert replication["entries_per_batch"] == 3.0  # group commit visible
    assert replication["group_commits"] == 1
    # Single-member quorum: nothing crossed the wire, so RTT is honestly
    # None — the KEY must still travel (trend lines key on it).
    assert "replication_rtt_ms_avg" in replication
    assert replication["reply_coalesce_ratio"] is None  # no remote origins
    assert replication["outbox_burst_avg"] == 2.0

    # Per-member stamps keep the same fields (trend-line attribution).
    member_stamp = section["node_stamps"]["Raft0"]
    assert member_stamp["entries_per_batch"] == 3.0
    assert member_stamp["raft_role"] == "leader"
    assert member_stamp["raft"]["append_frames"] == 0  # no peers: no wire
    assert member_stamp["transport"]["outbox_bursts"] == 1
    # And the latency table is intact next to them (first rung of the
    # round-15 ladder — the vectorized ingest plane raised the defaults).
    assert section["rates"]["60_tx_s"]["p99_ms"] == 20.0


def test_sub_min_rounds_pipelined_window_abstains_not_stale_rounds():
    """Round 18 abstention fix: a short pipelined leg delta-windowed
    against its warmup baseline must report first_bottleneck None — not
    the stale "rounds" verdict carried over from the cumulative
    counters of earlier (serial) legs."""
    from corda_tpu.obs import doctor as _doctor

    cumulative = {
        "verifier": "cpu",
        "raft": {"pipeline": True, "role": "leader"},
        # 100 cumulative rounds, pump-dominated — earlier legs' shape.
        "round_stage_s": {"rounds": 100, "pump": 3.0, "fsync": 0.2},
    }
    baseline = {"round_stage_s": {"rounds": 88, "pump": 2.99,
                                  "fsync": 0.05}}
    stale = _member_stamp(cumulative, "cpu")
    assert stale["busiest_stage"] == "pump"  # the carryover trap

    windowed = _member_stamp(cumulative, "cpu", baseline=baseline)
    # 12-round window < MIN_ATTRIBUTION_ROUNDS: honest abstention.
    assert windowed["busiest_stage"] is None
    sweep = SweepResult(
        results={}, node_stamps={"Raft0": windowed},
        doctor=_doctor.stamp_attribution({"Raft0": windowed}))
    assert sweep.first_bottleneck is None


def test_delta_window_reattributes_away_from_warmup_shape():
    """With enough rounds in the window, the delta stamp names what the
    MEASURED leg was bound by, not what warmup was."""
    cumulative = {"round_stage_s": {"rounds": 100, "pump": 3.0,
                                    "fsync": 1.5}}
    baseline = {"round_stage_s": {"rounds": 40, "pump": 2.99,
                                  "fsync": 0.1}}
    assert _member_stamp(cumulative, "cpu")["busiest_stage"] == "pump"
    windowed = _member_stamp(cumulative, "cpu", baseline=baseline)
    # 60-round window: pump delta is 0.01s, fsync delta is 1.4s.
    assert windowed["busiest_stage"] == "fsync"
    # Counter resets (member restart mid-sweep) clamp to zero, never
    # negative wall time.
    reset = _member_stamp(
        {"round_stage_s": {"rounds": 25, "pump": 0.5}}, "cpu",
        baseline={"round_stage_s": {"rounds": 0, "pump": 2.0}})
    assert reset["busiest_stage"] is None


def test_replication_summary_prefers_leader_then_busiest(tmp_path):
    stamp = _real_group_commit_stamp(tmp_path)
    follower = dict(stamp, role="follower", append_frames=999)
    quiet_leader = dict(stamp, role="leader", append_frames=3)
    busy_leader = dict(stamp, role="leader", append_frames=7)
    stamps = {"Raft0": {"raft": follower, "transport": None},
              "Raft1": {"raft": quiet_leader, "transport": None},
              "Raft2": {"raft": busy_leader, "transport": None}}
    summary = bench._replication_summary(stamps)
    # A follower's frame count never outranks a leader; among two partial
    # leader views (leader change mid-sweep) the busier one wrote the log.
    assert summary["member"] == "Raft2"
    assert bench._replication_summary({}) is None
    assert bench._replication_summary(
        {"Raft0": {"raft": None, "transport": None}}) is None
