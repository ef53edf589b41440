"""bench.py report assembly: one JSON line, and a non-zero exit whenever
the chip is missing or a phase fails.

The real phases need the TPU; here they are stubbed to validate the
progressive-report structure — the device phases run in-process instead
of in their own child, with the TPU check stubbed."""

import json
import os
import subprocess
import sys

import bench

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REAL_REQUIRE_JAX_FREE = bench._require_jax_free
_REAL_MULTICHIP = bench.bench_multichip_scaling


def _stub_phases(monkeypatch):
    from corda_tpu.testing import chip

    monkeypatch.setattr(chip, "require_tpu", lambda *a: {
        "platform": "tpu", "kind": "stub v5e", "count": 1})
    monkeypatch.setattr(bench, "_in_child", lambda fn: fn())
    # The test process has imported jax; the real check would refuse.
    monkeypatch.setattr(bench, "_require_jax_free", lambda phase: None)
    monkeypatch.setattr(bench, "_warm_verify_kernel", lambda: None)
    monkeypatch.setattr(bench, "warm_buckets", lambda *a: None)
    monkeypatch.setattr(bench, "bench_notary_roundtrip",
                        lambda **kw: {"tx_per_sec": 100.0})
    for name in ("bench_raft_cluster", "bench_open_loop_latency",
                 "bench_raft_open_loop",  # unstubbed, this one ran a REAL
                 # multiprocess raft sweep (and now a sidecar) inside every
                 # report test — minutes of suite time measuring nothing
                 "bench_validating_flagship",  # ditto: TWO flagship runs
                 "bench_shard_scaling",  # ditto: boots up to 4 raft groups
                 "bench_multichip_scaling",  # ditto: spawns 4 mesh sidecars
                 "bench_multihost_scaling",  # ditto: spawns up to 4
                 # federated sidecar hosts + a kill leg
                 "bench_slo_sweep",  # ditto: TWO full mixed-lane sweeps
                 "bench_ingest_sweep",  # ditto: builder + replay workers
                 "bench_telemetry",  # ditto: an in-process loadtest round
                 "bench_reshard",  # ditto: live split + merge in-process nets
                 "bench_durability",  # ditto: a bitrot chaos soak + fsck
                 "bench_partition_chaos",  # ditto: a THREE-leg split-brain
                 # soak (leader cut + prevote A/B) over real TCP clusters
                 "bench_chaos",  # ditto: a leader-kill soak
                 "bench_doctor",  # unstubbed, this one APPENDS to the
                 # checked-in artifacts/TRAJECTORY.jsonl from every report
                 # test — test pollution in the working tree
                 "bench_autotune",  # ditto: a real multiprocess baseline
                 # sweep plus budgeted candidate sweeps, AND it appends an
                 # autotune record to the checked-in trajectory store
                 "bench_vault_scaling",  # ditto: seeds 100k+-row sqlite
                 # vaults and replays a 100k-tx boot leg in-process
                 "bench_resolve_ids", "bench_trades", "bench_multisig",
                 "bench_partial_merkle", "bench_flow_churn"):
        monkeypatch.setattr(bench, name,
                            lambda *a, n=name, **kw: {"stub": n})
    monkeypatch.setattr(
        bench, "bench_kernel",
        lambda *a: ({4096: 1000.0}, {4096: 800.0}, {4096: 900.0},
                    {"kernel": {4096: "pallas"}, "e2e": {4096: "pallas"},
                     "e2e_devhash": {4096: "pallas"}}))
    monkeypatch.setattr(bench, "bench_stream",
                        lambda *a, **k: (1200.0, [1100.0, 1200.0], "pallas"))
    monkeypatch.setattr(bench, "bench_sha256", lambda: 5000.0)
    monkeypatch.setattr(bench, "bench_cpu_oracle", lambda *a: 250.0)


def _run(capsys):
    rc = bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    return rc, json.loads(out[0])


def _raise(exc):
    def fn(*a, **kw):
        raise exc
    return fn


def test_report_is_one_json_line(monkeypatch, capsys):
    _stub_phases(monkeypatch)
    rc, report = _run(capsys)
    assert rc == 0
    assert report["metric"] == "verified_sigs_per_sec"
    assert report["device"] == {"platform": "tpu", "kind": "stub v5e",
                                "count": 1}
    assert report["value"] == 1200.0  # stream beat the bucket numbers
    assert report["backend_by_phase"]["kernel"] == {"4096": "pallas"}
    assert report["vs_baseline"] == round(1200.0 / 50_000.0, 3)
    assert report["pallas_failures_total"] == 0
    configs = report["baseline_configs"]
    # Every config lands under one key whichever process measured it: the
    # device child (resolve_ids, trader_dvp, composite_3of3) or the
    # cluster phases of the parent.
    for key, stub in (
            ("raft_notary_3node", "bench_raft_cluster"),
            ("raft_validating_3node", "bench_validating_flagship"),
            ("raft_open_loop_latency", "bench_raft_open_loop"),
            ("open_loop_latency", "bench_open_loop_latency"),
            ("multichip_scaling", "bench_multichip_scaling"),
            ("multihost_scaling", "bench_multihost_scaling"),
            ("shard_scaling", "bench_shard_scaling"),
            ("slo_sweep", "bench_slo_sweep"),
            ("ingest_sweep", "bench_ingest_sweep"),
            ("telemetry", "bench_telemetry"),
            ("reshard", "bench_reshard"),
            ("autotune", "bench_autotune"),
            ("vault_scaling", "bench_vault_scaling"),
            ("resolve_ids", "bench_resolve_ids"),
            ("trader_dvp", "bench_trades"),
            ("composite_3of3", "bench_multisig"),
            ("partial_merkle", "bench_partial_merkle"),
            ("flow_churn", "bench_flow_churn")):
        assert configs[key] == {"stub": stub}, key
    assert report["notary_roundtrip"] == {"tx_per_sec": 100.0}
    assert report["chaos"] == {"stub": "bench_chaos"}
    assert report["durability"] == {"stub": "bench_durability"}
    assert report["partition_chaos"] == {"stub": "bench_partition_chaos"}
    # The doctor diagnoses the finished report, last.
    assert report["doctor"] == {"stub": "bench_doctor"}
    assert list(report["phase_seconds"])[-1] == "doctor"
    assert "phase" not in report and "error" not in report


def test_device_owning_phases_get_the_mesh_widths_the_chip_has(
        monkeypatch, capsys):
    _stub_phases(monkeypatch)
    from corda_tpu.testing import chip

    monkeypatch.setattr(chip, "require_tpu", lambda *a: {
        "platform": "tpu", "kind": "stub v5e", "count": 4})
    seen = {}
    monkeypatch.setattr(bench, "bench_multichip_scaling",
                        lambda **kw: seen.update(kw) or {"stub": 1})
    rc, report = _run(capsys)
    assert rc == 0
    assert seen["device_counts"] == (1, 2, 4)
    assert seen["notary_device"] == "accelerator"


def test_no_tpu_exits_nonzero_and_measures_nothing(monkeypatch, capsys):
    # No device: no phase may measure the host in the chip's place.
    _stub_phases(monkeypatch)
    from corda_tpu.testing import chip

    monkeypatch.setattr(chip, "require_tpu", _raise(
        chip.ChipError("JAX finds no TPU (platform 'cpu')")))
    rc, report = _run(capsys)
    assert rc != 0
    assert "no TPU" in report["error"]
    assert report["error_phase"] == "device_phases"
    assert report["value"] == 0.0
    assert "baseline_configs" not in report
    assert "notary_roundtrip" not in report


def test_device_phase_failure_exits_nonzero(monkeypatch, capsys):
    """A device phase that raises ends the run: no later phase measures,
    and the one line carries the failure."""
    _stub_phases(monkeypatch)
    monkeypatch.setattr(bench, "bench_kernel", _raise(
        RuntimeError("TPU device error - infrastructure failure")))
    rc, report = _run(capsys)
    assert rc != 0
    assert "TPU device error" in report["error"]
    assert report["value"] == 0.0  # headline never computed: honest zero
    assert "baseline_configs" not in report


def test_warm_failure_exits_nonzero(monkeypatch, capsys):
    _stub_phases(monkeypatch)
    monkeypatch.setattr(bench, "warm_buckets", _raise(
        RuntimeError("UNAVAILABLE: TPU device error")))
    rc, report = _run(capsys)
    assert rc != 0
    assert "UNAVAILABLE" in report["error"]
    assert "baseline_configs" not in report
    assert report["value"] == 0.0


def test_cluster_phase_failure_keeps_what_finished(monkeypatch, capsys):
    _stub_phases(monkeypatch)
    monkeypatch.setattr(bench, "bench_flow_churn",
                        _raise(RuntimeError("boom")))
    rc, report = _run(capsys)
    assert rc != 0
    assert report["error"] == "RuntimeError: boom"
    assert report["error_phase"] == "flow_churn"
    assert report["value"] == 1200.0  # the device child's headline landed
    assert report["baseline_configs"]["partial_merkle"] == {
        "stub": "bench_partial_merkle"}
    assert "flow_churn" not in report["baseline_configs"]
    assert "doctor" not in report


def test_mesh_sidecar_failure_on_the_chip_exits_nonzero(monkeypatch, capsys):
    """A mesh width whose sidecar round raises on the chip (a warm-up that
    ends the sidecar, a client fallback to the host tier) ends the run: it
    is not kept as a per-width error entry."""
    _stub_phases(monkeypatch)
    monkeypatch.setattr(bench, "bench_multichip_scaling", _REAL_MULTICHIP)
    monkeypatch.setattr(bench, "_mesh_sidecar_round", _raise(RuntimeError(
        "mesh1 sidecar answered from the host tier (3 client fallbacks)")))
    rc, report = _run(capsys)
    assert rc != 0
    assert report["error_phase"] == "multichip_scaling"
    assert "host tier" in report["error"]
    assert "multichip_scaling" not in report["baseline_configs"]


def test_mesh_flagship_failure_on_the_chip_exits_nonzero(monkeypatch, capsys):
    _stub_phases(monkeypatch)
    monkeypatch.setattr(bench, "bench_multichip_scaling", _REAL_MULTICHIP)
    monkeypatch.setattr(bench, "_mesh_sidecar_round",
                        lambda devices, **kw: {"sigs_per_sec": 1.0})
    monkeypatch.setattr(bench, "bench_raft_cluster", _raise(TimeoutError(
        "sidecar did not finish its device warm-up in 420 s")))
    rc, report = _run(capsys)
    assert rc != 0
    assert report["error_phase"] == "multichip_scaling"
    assert "warm-up" in report["error"]


def test_device_owning_phases_need_a_jax_free_parent(monkeypatch, capsys):
    """A parent that has imported jax, as a bench parent must not, is
    refused by the first phase that hands the chip to a child."""
    import jax  # noqa: F401 - the point of the test

    _stub_phases(monkeypatch)
    monkeypatch.setattr(bench, "_require_jax_free", _REAL_REQUIRE_JAX_FREE)
    called = []
    monkeypatch.setattr(bench, "bench_validating_flagship",
                        lambda **kw: called.append(kw))
    rc, report = _run(capsys)
    assert rc != 0
    assert report["error_phase"] == "raft_validating_3node"
    assert "imported jax" in report["error"]
    assert called == []


def test_bench_without_a_tpu_exits_nonzero():
    # The real path, as a user runs it, on this host (JAX finds no TPU).
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=ROOT, capture_output=True,
        text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "no TPU" in report["error"]
    assert report["value"] == 0.0


def _fake_multiprocess_result(sidecar=None, stamps=None):
    from corda_tpu.tools.loadtest import MultiProcessResult

    return MultiProcessResult(
        tx_requested=8, tx_committed=8, tx_rejected=0, width=4, clients=2,
        duration_s=1.0, wall_s=1.5, tx_per_sec=8.0, sigs_verified=32,
        sigs_per_sec=32.0, p50_ms=5.0, p99_ms=9.0,
        node_stamps=stamps if stamps is not None else {},
        sidecar=sidecar)


def test_raft_cluster_report_carries_sidecar_and_occupancy(monkeypatch):
    """The one-line-JSON contract for the sidecar rollout: BOTH the
    device-ish (sidecar=True) and the host-only default paths must emit
    the sidecar + device_occupancy keys, so trend tooling never branches
    on schema."""
    from corda_tpu.tools import loadtest

    server_stats = {"batches": 2, "sigs": 80, "cross_request_batches": 1,
                    "batch_sigs_hist": {"256": 2}}
    stamps = {"Raft0": {"device_batches": 3, "host_batches": 1},
              "Raft1": {"device_batches": 0, "host_batches": 0}}
    monkeypatch.setattr(
        loadtest, "run_loadtest_multiprocess",
        lambda **kw: _fake_multiprocess_result(
            sidecar=server_stats if kw.get("sidecar") else None,
            stamps=stamps))

    dev = bench.bench_raft_cluster(n_tx=8, sidecar=True)
    assert dev["sidecar"] == server_stats
    assert dev["device_batches"] == 3
    assert dev["host_batches"] == 1
    assert dev["device_occupancy"] == 0.75

    host = bench.bench_raft_cluster(n_tx=8)  # host-only default path
    assert "sidecar" in host and host["sidecar"] is None
    assert host["device_occupancy"] == 0.75  # same aggregation either way

    # Zero batches anywhere: occupancy is an honest 0.0, never a crash.
    monkeypatch.setattr(
        loadtest, "run_loadtest_multiprocess",
        lambda **kw: _fake_multiprocess_result(stamps={"Raft0": {}}))
    empty = bench.bench_raft_cluster(n_tx=8)
    assert empty["device_occupancy"] == 0.0
    assert empty["sidecar"] is None


def test_raft_open_loop_report_carries_sidecar_and_occupancy(monkeypatch):
    import types

    from corda_tpu.tools import loadtest

    rate_result = types.SimpleNamespace(p50_ms=4.0, p90_ms=6.0, p99_ms=8.0,
                                        tx_per_sec=30.0, committed=200)
    server_stats = {"batches": 5, "sigs": 400}

    def fake_sweep(**kw):
        return loadtest.SweepResult(
            results={30.0: rate_result},
            node_stamps={"Raft0": {"device_batches": 4, "host_batches": 4}},
            trace_snapshots=[],
            sidecar=server_stats if kw.get("sidecar") else None)

    monkeypatch.setattr(loadtest, "run_latency_sweep", fake_sweep)

    dev = bench.bench_raft_open_loop(rates=(30.0,), n_tx=200, sidecar=True)
    assert dev["sidecar"] == server_stats
    assert dev["device_occupancy"] == 0.5
    assert dev["rates"]["30_tx_s"]["p99_ms"] == 8.0

    host = bench.bench_raft_open_loop(rates=(30.0,), n_tx=200)
    assert "sidecar" in host and host["sidecar"] is None
    assert "device_occupancy" in host


def test_shard_scaling_report_contract(monkeypatch):
    """The shard_scaling section's one-line-JSON contract: one entry per
    shard count carrying throughput + the per-group ledger audit, plus the
    cross_shard_mix adversarial section whose exactly_once verdict and
    ledger-row arithmetic (expected = committed + cross_committed) must
    always be present — trend tooling greps these keys flat."""
    from corda_tpu.tools import loadtest

    calls = []

    def fake_mp(**kw):
        calls.append(kw)
        shards = kw["shards"]
        committed = kw["n_tx"]
        cross = committed // 2 if kw.get("cross_frac") else 0
        r = _fake_multiprocess_result()
        r.shards = shards
        r.tx_committed = committed
        r.tx_per_sec = 50.0 * shards  # monotone: the acceptance trend
        r.cross_requested = cross
        r.cross_committed = cross
        r.per_group_committed = [committed // shards] * shards
        r.ledger_committed = committed + cross
        r.ledger_expected = committed + cross
        r.reserved_leaked = 0
        r.exactly_once = True
        return r

    monkeypatch.setattr(loadtest, "run_loadtest_multiprocess", fake_mp)
    out = bench.bench_shard_scaling(shard_counts=(1, 2, 4), n_tx=8)

    assert set(out["shards"]) == {"1", "2", "4"}
    trend = [out["shards"][k]["tx_per_sec"] for k in ("1", "2", "4")]
    assert trend == sorted(trend)  # the acceptance bar the bench states
    for section in out["shards"].values():
        assert section["exactly_once"] is True
        assert "per_group_committed" in section
        assert "p99_ms" in section
    mix = out["cross_shard_mix"]
    assert mix["shards"] == 2 and mix["cross_frac"] == 0.5
    assert mix["ledger_committed"] == mix["ledger_expected"]
    assert mix["reserved_leaked"] == 0
    assert mix["exactly_once"] is True
    # The adversarial run actually asked for the 2PC mix.
    assert calls[-1]["cross_frac"] == 0.5 and calls[-1]["shards"] == 2
    # And every run used real OS-process groups of 1 member.
    assert all(kw["cluster_size"] == 1 for kw in calls)


def test_multichip_scaling_report_contract(monkeypatch):
    """The multichip_scaling section's one-line-JSON contract: one entry
    per mesh width carrying parity-checked sigs/s + pad/occupancy
    attribution, the flat sigs_per_sec_by_devices trend (monotone
    non-decreasing on a mesh-capable harness — the acceptance bar),
    scaling_1_to_max, and per-config error isolation. Mirrors the
    shard_scaling contract so trend tooling greps both the same way."""
    calls = []

    def fake_round(devices, **kw):
        calls.append((devices, kw))
        return {"devices": devices, "n_sigs": kw.get("n_sigs", 4096),
                "rounds": kw.get("rounds", 5),
                "sigs_per_sec": 10_000.0 * devices,  # near-linear
                "p50_ms": 8.0 / devices, "p99_ms": 12.0 / devices,
                "parity_ok": True, "client_fallbacks": 0,
                "mesh_devices": devices, "warm_error": None,
                "pad_fraction": 0.01,
                "per_device_occupancy": 0.99,
                "per_device_batch_sigs_hist": {str(4096 // devices): 5}}

    monkeypatch.setattr(bench, "_mesh_sidecar_round", fake_round)
    monkeypatch.setattr(bench, "bench_raft_cluster",
                        lambda **kw: {"stub": "flagship", **kw})

    out = bench.bench_multichip_scaling(device_counts=(1, 2, 4, 8),
                                        notary_device="accelerator",
                                        flagship=True)
    assert out["mesh"] == "device"
    assert set(out["devices"]) == {"1", "2", "4", "8"}
    trend = [out["sigs_per_sec_by_devices"][k] for k in ("1", "2", "4", "8")]
    assert trend == sorted(trend)  # monotone: the acceptance bar
    assert out["scaling_1_to_max"] == 8.0  # >= 6x at 8 vs 1 passes
    for section in out["devices"].values():
        assert section["parity_ok"] is True
        assert section["warm_error"] is None
        assert "per_device_occupancy" in section
        assert "pad_fraction" in section
    # The flagship ran the production topology fed by the widest mesh.
    flag = out["flagship_mesh_sidecar"]
    assert flag["sidecar"] is True and flag["sidecar_devices"] == 8
    assert flag["notary_device"] == "accelerator"
    # Every round targeted the requested harness.
    assert [d for d, _ in calls] == [1, 2, 4, 8]
    assert all(kw["notary_device"] == "accelerator" for _, kw in calls)

    # Virtual CPU mesh, no flagship: one failing width must not take down
    # the section (per-config error isolation; on the chip it raises).
    def flaky_round(devices, **kw):
        if devices == 4:
            raise RuntimeError("mesh boot failed")
        return fake_round(devices, **kw)

    monkeypatch.setattr(bench, "_mesh_sidecar_round", flaky_round)
    host = bench.bench_multichip_scaling(device_counts=(1, 2, 4),
                                         n_sigs=1024, rounds=3)
    assert host["mesh"] == "virtual-cpu"
    assert "flagship_mesh_sidecar" not in host
    assert host["devices"]["4"] == {"error": "RuntimeError: mesh boot failed"}
    assert set(host["sigs_per_sec_by_devices"]) == {"1", "2"}
    assert "scaling_1_to_max" not in host  # max width errored: no ratio


def test_multihost_scaling_report_contract(monkeypatch):
    """The multihost_scaling section's one-line-JSON contract: one entry
    per simulated-host count carrying parity-checked sigs/s + the
    router's routing-share attribution, the flat sigs_per_sec_by_hosts
    trend (monotone non-decreasing — the acceptance bar), the host-kill
    leg's exactly_once audit, and per-width error isolation. Mirrors
    multichip_scaling so trend tooling greps both the same way."""
    calls = []

    def fake_round(hosts, **kw):
        calls.append((hosts, kw))
        out = {"hosts": hosts, "n_sigs": kw.get("n_sigs", 16),
               "workers": 2 * hosts, "batches": 40 * hosts,
               "sigs_per_sec": 120.0 * hosts,  # near-linear
               "p50_ms": 130.0, "p99_ms": 180.0, "parity_ok": True,
               "fallbacks": 0, "hedges": 0, "host_degraded": 0,
               "federation": {"routing_share_by_host": {
                   f"h{i}": round(1.0 / hosts, 4) for i in range(hosts)}}}
        if kw.get("kill_after_s") is not None:
            out["host_kill"] = {"killed_host": "h0", "exactly_once": True,
                                "answered_batches": 35,
                                "post_kill_dispatches_by_host": [0, 15],
                                "survivor_share_post_kill": 1.0,
                                "host_degraded": 1, "local_fallbacks": 1}
        return out

    monkeypatch.setattr(bench, "_federation_round", fake_round)
    out = bench.bench_multihost_scaling(host_counts=(1, 2, 4))
    # The simulated-host disclosure is part of the schema: these numbers
    # come from sidecar processes sharing one box, not a real pod.
    assert out["mesh"] == "virtual-cpu"
    assert out["simulated_hosts"] is True
    assert set(out["hosts"]) == {"1", "2", "4"}
    trend = [out["sigs_per_sec_by_hosts"][k] for k in ("1", "2", "4")]
    assert trend == sorted(trend)  # monotone: the acceptance bar
    assert out["scaling_1_to_max"] == 4.0  # >=1.7x@2, >=3x@4 passes
    for section in out["hosts"].values():
        assert section["parity_ok"] is True
        assert "routing_share_by_host" in section["federation"]
    # The kill leg ran on 2 hosts and its audit is hoisted to the top.
    assert out["host_kill"]["exactly_once"] is True
    assert out["host_kill"]["survivor_share_post_kill"] == 1.0
    assert [h for h, _ in calls] == [1, 2, 4, 2]
    assert calls[-1][1]["kill_after_s"] is not None

    # One failing width must not take down the section — and a failed
    # max width means no honest scaling ratio.
    def flaky_round(hosts, **kw):
        if hosts == 4:
            raise RuntimeError("host boot failed")
        return fake_round(hosts, **kw)

    monkeypatch.setattr(bench, "_federation_round", flaky_round)
    host = bench.bench_multihost_scaling(host_counts=(1, 2, 4),
                                         kill_leg=False)
    assert host["hosts"]["4"] == {"error": "RuntimeError: host boot failed"}
    assert set(host["sigs_per_sec_by_hosts"]) == {"1", "2"}
    assert "scaling_1_to_max" not in host
    assert "host_kill" not in host

    # A kill leg that dies mid-run is isolated the same way.
    def kill_flaky(hosts, **kw):
        if kw.get("kill_after_s") is not None:
            raise RuntimeError("kill leg hung")
        return fake_round(hosts, **kw)

    monkeypatch.setattr(bench, "_federation_round", kill_flaky)
    out = bench.bench_multihost_scaling(host_counts=(1, 2))
    assert out["host_kill"] == {"error": "RuntimeError: kill leg hung"}
    assert set(out["sigs_per_sec_by_hosts"]) == {"1", "2"}


def test_slo_sweep_report_contract(monkeypatch):
    """The slo_sweep section's one-line-JSON contract: per-lane p50/p99 at
    every offered load for BOTH the armed run and the no-QoS baseline,
    plus the explicit SLO verdict (interactive p99 within bound at the
    ≥5×-flagship top rate while bulk sheds, baseline collapse ratio) —
    trend tooling and the driver grep these keys flat, and the whole
    section must survive json.dumps (FirehoseResults never leak through)."""
    from corda_tpu.tools import loadtest
    from corda_tpu.tools.loadgen import FirehoseResult

    def fr(p99, shed=0, lane=""):
        return FirehoseResult(
            requested=120, committed=120 - shed, rejected=shed,
            duration_s=2.0, tx_per_sec=60.0, p50_ms=p99 / 4, p90_ms=p99 / 2,
            p99_ms=p99, width=4, sigs_signed=480, lane=lane, shed=shed)

    calls = []

    def fake_sweep(**kw):
        calls.append(kw)
        if kw["qos"]:  # armed: interactive flat, bulk shed under overload
            results = {60.0: {"interactive": fr(40.0, lane="interactive"),
                              "bulk": fr(60.0, lane="bulk")},
                       240.0: {"interactive": fr(120.0, lane="interactive"),
                               "bulk": fr(900.0, shed=35, lane="bulk")}}
            return loadtest.SweepResult(
                results=results,
                node_stamps={"Notary": {"device_batches": 0}},
                qos={"Notary": {"qos": {"interactive_flows": 30},
                                "admission": {"shed_bulk": 35}}})
        results = {60.0: {"interactive": fr(50.0, lane="interactive"),
                          "bulk": fr(55.0, lane="bulk")},
                   240.0: {"interactive": fr(2400.0, lane="interactive"),
                           "bulk": fr(2500.0, lane="bulk")}}
        return loadtest.SweepResult(results=results, node_stamps={})

    monkeypatch.setattr(loadtest, "run_slo_sweep", fake_sweep)
    out = bench.bench_slo_sweep(rates=(60.0, 240.0), slo_ms=250.0,
                                flagship_tx_s=40.0)

    json.dumps(out)  # the one-line contract: fully serializable
    # Both runs happened, armed first, over the same rates.
    assert [kw["qos"] for kw in calls] == [True, False]
    assert calls[0]["rates"] == calls[1]["rates"] == (60.0, 240.0)
    # Round 16: only the ARMED run gets the flight-recorder dump dir (the
    # baseline exists to collapse — dumping its breach would be noise),
    # and the section surfaces the dir + artifact list even when the
    # sweep result predates the telemetry fields (getattr-compat).
    assert calls[0]["flight_dir"] and "flight_dir" not in calls[1]
    assert out["flight"]["dir"] == calls[0]["flight_dir"]
    assert out["flight"]["artifacts"] == []
    assert out["cluster_telemetry"] is None
    # Per-lane percentiles at every rate, both sections.
    assert out["qos"]["240_tx_s"]["interactive"]["p99_ms"] == 120.0
    assert out["qos"]["240_tx_s"]["bulk"]["shed"] == 35
    assert out["no_qos_baseline"]["240_tx_s"]["interactive"]["p99_ms"] \
        == 2400.0
    # Member-side plane + admission stats ride along.
    assert out["member_qos"]["Notary"]["admission"]["shed_bulk"] == 35
    # The verdict: within bound at 6× flagship, bulk shed, baseline
    # collapsed 20× worse.
    v = out["verdict"]
    assert v["offered_top_tx_s"] == 240.0
    assert v["offered_over_flagship"] == 6.0
    assert v["interactive_p99_within_slo"] is True
    assert v["bulk_shed_nonzero"] is True
    assert v["interactive_vs_baseline"] == 20.0
    assert v["slo_met"] is True

    # SLO breach shape: interactive p99 over the bound flips the verdict
    # (the section reports the miss, it does not hide it).
    monkeypatch.setattr(
        loadtest, "run_slo_sweep",
        lambda **kw: loadtest.SweepResult(results={
            240.0: {"interactive": fr(900.0, lane="interactive"),
                    "bulk": fr(950.0, lane="bulk")}}))
    miss = bench.bench_slo_sweep(rates=(240.0,), slo_ms=250.0)
    assert miss["verdict"]["interactive_p99_within_slo"] is False
    assert miss["verdict"]["slo_met"] is False

    # Measured-saturation calibration rides the section: derived per-lane
    # admission rates with provenance, serializable, and honest about a
    # sweep where no rate met the SLO.
    cal = out["calibration"]
    json.dumps(cal)
    assert cal["met_slo"] is True
    assert cal["saturation_rate"] == 240.0
    assert cal["interactive_rate"] > 0 and cal["bulk_rate"] > 0
    assert miss["calibration"]["met_slo"] is False


def _fake_ingest_row(rate, achieved=None, exactly_once=True):
    return {"offered_tx_s": float(rate),
            "achieved_tx_s": achieved if achieved is not None else rate * 0.8,
            "requested": 2000, "committed": 2000, "rejected": 0,
            "duration_s": 2.0, "p50_ms": 5.0, "p99_ms": 40.0, "workers": 3,
            "frames_per_tx": 1.4, "exactly_once": exactly_once,
            "ingest": {"tx_built_per_s": 1800.0, "sigs_signed_per_s": 9000.0,
                       "serialize_ms": 120.0, "prepare_s": 1.1,
                       "bytes_written": 1 << 20, "sigs_signed": 4000,
                       "cpu_s": 3.2, "load_prepare_s": 0.4}}


def test_ingest_sweep_report_contract(monkeypatch):
    """The ingest_sweep section's one-line-JSON contract (round 15): one
    row per offered rate carrying the client-plane attribution block
    (tx_built_per_s / sigs_signed_per_s / serialize_ms / cpu_s), the
    frames-per-tx amortization, the exactly-once audit, the monotonic
    offered-rate trend, per-sub-run error isolation, and the
    first_bottleneck server-side attribution — identical schema on the
    device and host-only phase paths (both registries call this one
    function with no path-specific args)."""
    from corda_tpu.tools import loadtest

    calls = []

    def fake_sweep(**kw):
        calls.append(kw)
        if kw.get("chaos"):
            return loadtest.SweepResult(
                results={1200.0: _fake_ingest_row(1200.0)},
                node_stamps={})
        return loadtest.SweepResult(
            results={r: _fake_ingest_row(r) for r in kw["rates"]},
            node_stamps={
                "Raft0": {"busiest_stage": "fsync"},
                "Raft1": {"busiest_stage": "fsync"},
                "Raft2": {"busiest_stage": "verify"}})

    monkeypatch.setattr(loadtest, "run_ingest_sweep", fake_sweep)
    out = bench.bench_ingest_sweep(rates=(1200.0, 3600.0, 10000.0))

    json.dumps(out)  # the one-line contract: fully serializable
    # Main ladder clean, chaos leg armed with the lossy plan.
    assert calls[0].get("chaos") is None and calls[1]["chaos"] == "lossy"
    # The offered ladder is monotonic and every row carries its rate —
    # the trend tooling reads the rows in rate order.
    offered = [out["rates"][f"{r:g}_tx_s"]["offered_tx_s"]
               for r in (1200.0, 3600.0, 10000.0)]
    assert offered == sorted(offered)
    assert out["offered_rates_tx_s"] == offered
    # Client-plane attribution block rides every row.
    row = out["rates"]["3600_tx_s"]
    assert row["ingest"]["tx_built_per_s"] == 1800.0
    assert row["ingest"]["sigs_signed_per_s"] == 9000.0
    assert row["frames_per_tx"] == 1.4
    # Headline keys, flat.
    assert out["peak_offered_tx_s"] == 10000.0
    assert out["peak_achieved_tx_s"] == 8000.0
    assert out["exactly_once_all"] is True
    # Server-side attribution: the doctor's evidence-ranked verdict over
    # the member stamps (majority busiest stage wins here), with the full
    # ranked list + evidence riding under "doctor".
    assert out["first_bottleneck"] == "fsync"
    assert out["doctor"]["first_bottleneck"] == "fsync"
    top = out["doctor"]["bottlenecks"][0]
    assert top["cause"] == "fsync"
    assert top["evidence"]["busiest_stage_by_member_count"] == {
        "fsync": 2, "verify": 1}
    assert top["next_experiment"]  # every entry names its next move
    # Chaos leg verdict: exactly-once held under the lossy plan.
    assert out["chaos"]["plan"] == "lossy"
    assert out["chaos"]["exactly_once"] is True


def test_ingest_sweep_pipeline_delta_contract(monkeypatch):
    """Round 18: after the chaos leg the section runs a serial-vs-
    pipelined raft A/B at one rate and reports the committed-tx/s delta
    — the number `perfdoctor --gate` regresses on. Both legs must pin
    notary="raft" (the delta is about the commit plane, not the simple
    notary) and differ ONLY in the [raft] pipeline flag."""
    from corda_tpu.tools import loadtest

    calls = []

    def fake_sweep(**kw):
        calls.append(kw)
        if kw.get("chaos"):
            return loadtest.SweepResult(
                results={1200.0: _fake_ingest_row(1200.0)}, node_stamps={})
        rate = kw["rates"][0]
        # The pipelined leg commits 2.5x the serial leg's throughput.
        achieved = rate * (2.0 if kw.get("pipeline", True) else 0.8)
        return loadtest.SweepResult(
            results={r: _fake_ingest_row(r, achieved=achieved)
                     for r in kw["rates"]},
            node_stamps={})

    monkeypatch.setattr(loadtest, "run_ingest_sweep", fake_sweep)
    out = bench.bench_ingest_sweep(rates=(1200.0,))
    json.dumps(out)

    # Main ladder + chaos leg first, then the two delta legs.
    assert calls[1]["chaos"] == "lossy"
    serial_kw, piped_kw = calls[2], calls[3]
    assert serial_kw["pipeline"] is False and piped_kw["pipeline"] is True
    for kw in (serial_kw, piped_kw):
        assert kw["notary"] == "raft"
        assert kw["rates"] == (2400.0,)

    delta = out["pipeline_delta"]
    assert delta["notary"] == "raft"
    assert delta["rate_tx_s"] == 2400.0
    assert delta["committed_tx_s_serial"] == 1920.0
    assert delta["committed_tx_s_pipelined"] == 4800.0
    assert delta["pipeline_speedup"] == 2.5
    assert delta["exactly_once_both"] is True


def test_ingest_sweep_pipeline_delta_crash_costs_only_its_key(monkeypatch):
    from corda_tpu.tools import loadtest

    def fake_sweep(**kw):
        if "pipeline" in kw:
            raise RuntimeError("delta leg worker died")
        if kw.get("chaos"):
            return loadtest.SweepResult(
                results={1200.0: _fake_ingest_row(1200.0)}, node_stamps={})
        return loadtest.SweepResult(
            results={r: _fake_ingest_row(r) for r in kw["rates"]},
            node_stamps={})

    monkeypatch.setattr(loadtest, "run_ingest_sweep", fake_sweep)
    out = bench.bench_ingest_sweep(rates=(1200.0,))
    json.dumps(out)
    assert "RuntimeError" in out["pipeline_delta"]["error"]
    assert out["chaos"]["exactly_once"] is True  # earlier legs unharmed
    assert out["peak_achieved_tx_s"] == 960.0


def test_ingest_sweep_report_isolates_subrun_errors(monkeypatch):
    """One failed rate (dead worker, timeout) records an error row and the
    later rates still report; headline aggregates come from the rates that
    finished — and a chaos-leg crash costs only the chaos key."""
    from corda_tpu.tools import loadtest

    def fake_sweep(**kw):
        if kw.get("chaos"):
            raise RuntimeError("worker died mid-replay")
        return loadtest.SweepResult(
            results={
                1200.0: _fake_ingest_row(1200.0),
                3600.0: {"error": "TimeoutError: replay@3600 stalled",
                         "offered_tx_s": 3600.0},
                10000.0: _fake_ingest_row(10000.0)},
            node_stamps={})

    monkeypatch.setattr(loadtest, "run_ingest_sweep", fake_sweep)
    out = bench.bench_ingest_sweep(rates=(1200.0, 3600.0, 10000.0))
    json.dumps(out)
    assert "TimeoutError" in out["rates"]["3600_tx_s"]["error"]
    assert out["rates"]["10000_tx_s"]["committed"] == 2000
    assert out["peak_achieved_tx_s"] == 8000.0
    assert out["exactly_once_all"] is False  # an errored rate is not audited
    assert out["first_bottleneck"] is None  # no stamps: honest null
    assert "error" in out["chaos"]


def _fake_reshard_result(**over):
    base = dict(
        plan="reshard", epoch=1, from_shards=2, to_shards=4,
        direction="split", tx_requested=200, tx_committed=200,
        tx_rejected=0, tx_unresolved=0, exactly_once=True,
        cluster_committed=240, per_group_committed=[60, 60, 60, 60],
        reserved_leaked=0, cross_requested=40, wrong_epoch_bounces=6,
        handoff_frames=4, reshard_started_s=1.0, reshard_completed_s=1.8,
        duration_s=5.0, tx_per_sec=40.0, p50_ms=80.0, p99_ms=300.0,
        p99_before_ms=100.0, p99_during_ms=280.0, p99_after_ms=120.0,
        faults_injected={"shard.handoff:drop": 2})
    base.update(over)
    from corda_tpu.tools.loadtest import ReshardResult
    return ReshardResult(**base)


def test_reshard_report_contract(monkeypatch):
    """The reshard section's one-line-JSON contract: a chaos-armed live
    SPLIT followed by a clean MERGE back, with the headline verdict keys
    hoisted flat (exactly_once across BOTH runs, bounded wrong_epoch
    bounces, the transition window, and the before/during/after p99s that
    substantiate 'a blip, not an outage') — trend tooling greps these
    flat on the device and host-only phase paths alike."""
    from corda_tpu.tools import loadtest

    calls = []

    def fake_reshard(**kw):
        calls.append(kw)
        if kw.get("plan") == "reshard":
            return _fake_reshard_result()
        return _fake_reshard_result(
            plan=None, from_shards=4, to_shards=2, direction="merge",
            wrong_epoch_bounces=2, cross_requested=0, cluster_committed=100,
            tx_requested=100, tx_committed=100,
            per_group_committed=[50, 50, 0, 0], faults_injected={})

    monkeypatch.setattr(loadtest, "run_reshard_loadtest", fake_reshard)
    out = bench.bench_reshard(n_tx=200, rate_tx_s=80.0)

    json.dumps(out)  # the one-line contract: fully serializable
    # The split ran under the armed builtin chaos plan; the merge clean,
    # with the shard counts swapped back.
    assert calls[0]["plan"] == "reshard" and calls[0]["cross_frac"] == 0.2
    assert (calls[0]["shards"], calls[0]["to_shards"]) == (2, 4)
    assert calls[1]["plan"] is None
    assert (calls[1]["shards"], calls[1]["to_shards"]) == (4, 2)
    # Headline keys, flat.
    assert out["exactly_once"] is True
    assert out["wrong_epoch_bounces"] == 6
    assert out["handoff_frames"] == 4
    assert out["reshard_window_s"] == 0.8
    assert out["p99_before_ms"] == 100.0
    assert out["p99_during_ms"] == 280.0
    assert out["p99_after_ms"] == 120.0
    assert out["faults_injected"] == {"shard.handoff:drop": 2}
    # Full audits ride under split/merge.
    assert out["split"]["direction"] == "split"
    assert out["split"]["per_group_committed"] == [60, 60, 60, 60]
    assert out["merge"]["direction"] == "merge"

    # Either run failing the audit flips the headline verdict — the
    # section reports the miss, it does not hide it.
    monkeypatch.setattr(
        loadtest, "run_reshard_loadtest",
        lambda **kw: _fake_reshard_result(
            exactly_once=(kw.get("plan") == "reshard"),
            reshard_completed_s=None))
    bad = bench.bench_reshard(n_tx=200)
    assert bad["exactly_once"] is False
    assert bad["reshard_window_s"] is None  # never completed: honest null


def test_validating_flagship_adaptive_ab_contract(monkeypatch):
    """The flagship A/B contract: raft_validating_3node runs static-window
    then adaptive-window coalescing, the section IS the armed run (flat
    keys unchanged for trend tooling), and the static counterpart plus the
    arming verdict ride under adaptive_coalesce_ab."""
    calls = []

    def fake_cluster(**kw):
        calls.append(kw)
        adaptive = kw.get("adaptive_coalesce")
        return {"tx_per_sec": 44.0 if adaptive else 40.0, "p50_ms": 90.0,
                "p99_ms": 250.0 if adaptive else 260.0,
                "loadtest_sigs_per_sec": 700.0,
                "sidecar": {"batches": 3}}

    monkeypatch.setattr(bench, "bench_raft_cluster", fake_cluster)
    out = bench.bench_validating_flagship(verifier="jax",
                                          notary_device="accelerator")

    json.dumps(out)
    # Both runs happened, static first, on the flagship topology.
    assert [kw["adaptive_coalesce"] for kw in calls] == [False, True]
    assert all(kw["notary"] == "raft-validating" and kw["sidecar"]
               for kw in calls)
    assert all(kw["notary_device"] == "accelerator" for kw in calls)
    # The section IS the armed run; the A/B rides alongside.
    assert out["tx_per_sec"] == 44.0
    ab = out["adaptive_coalesce_ab"]
    assert ab["static"]["tx_per_sec"] == 40.0
    assert ab["adaptive"]["tx_per_sec"] == 44.0
    assert ab["tx_per_sec_ratio"] == 1.1
    assert ab["p99_ratio"] == round(250.0 / 260.0, 3)
    assert ab["adaptive_no_worse"] is True

    # Adaptive tanking throughput flips the arming verdict.
    monkeypatch.setattr(
        bench, "bench_raft_cluster",
        lambda **kw: {"tx_per_sec": 20.0 if kw.get("adaptive_coalesce")
                      else 40.0, "p50_ms": 90.0, "p99_ms": 260.0,
                      "loadtest_sigs_per_sec": 1.0, "sidecar": None})
    bad = bench.bench_validating_flagship()
    assert bad["adaptive_coalesce_ab"]["adaptive_no_worse"] is False


def test_verifier_stamp_reports_device_occupancy():
    class FakeVerifier:
        name = "jax-batch"
        device_min_sigs = 512
        device_batches = 9
        host_batches = 3

    stamp = bench._verifier_stamp(FakeVerifier())
    assert stamp["device_occupancy"] == 0.75
    FakeVerifier.device_batches = 0
    FakeVerifier.host_batches = 0
    assert bench._verifier_stamp(FakeVerifier())["device_occupancy"] == 0.0


def test_total_crash_still_prints_one_line(monkeypatch, capsys):
    """Even an exception before any phase ran produces the one-line
    report with the crash attributed, and a non-zero exit."""
    _stub_phases(monkeypatch)
    monkeypatch.setattr(bench, "make_corpus", _raise(
        RuntimeError("totally unexpected")))
    rc, report = _run(capsys)
    assert rc != 0
    assert "totally unexpected" in report["error"]


def _fake_chaos_result(**over):
    from corda_tpu.tools.loadtest import ChaosResult

    base = dict(
        plan="bitrot", tx_requested=60, tx_committed=60, tx_rejected=0,
        tx_unresolved=0, exactly_once=True, cluster_committed=60,
        duration_s=4.0, tx_per_sec=15.0, p50_ms=40.0, p99_ms=220.0,
        faults_injected={"disk.corrupt:flip": 3},
        integrity_errors=3, fsck_clean=True)
    base.update(over)
    return ChaosResult(**base)


def test_durability_report_contract(monkeypatch):
    """The durability section's one-line-JSON contract (round 14): a
    bitrot chaos soak whose corruption is detected AND healed with the
    exactly-once audit intact, plus the cold detect/repair micro — with
    the verdict keys hoisted flat (exactly_once, integrity_errors,
    fsck_clean, detect_ms, repair_s) so trend tooling greps them on the
    device and host-only phase paths alike."""
    from corda_tpu.tools import loadtest

    calls = []

    def fake_chaos(**kw):
        calls.append(kw)
        return _fake_chaos_result()

    monkeypatch.setattr(loadtest, "run_chaos_loadtest", fake_chaos)
    out = bench.bench_durability(n_tx=60, micro_rows=64)

    json.dumps(out)  # the one-line contract: fully serializable
    assert calls[0]["plan"] == "bitrot"
    # Headline keys, flat.
    assert out["exactly_once"] is True
    assert out["integrity_errors"] == 3
    assert out["fsck_clean"] is True
    # The micro ran for REAL on a cold store: one corrupted row found,
    # detection latency and repair time measured, store clean afterwards.
    micro = out["detect_repair_micro"]
    assert micro["corrupt_found"] == 1
    assert micro["clean_after_repair"] is True
    assert out["detect_ms"] > 0.0
    assert out["repair_s"] > 0.0
    # Full audit rides under the sub-run key.
    assert out["bitrot_chaos"]["faults_injected"] == {"disk.corrupt:flip": 3}


def test_durability_report_isolates_subrun_errors(monkeypatch):
    """A chaos sub-run failure must cost only its own keys: the micro
    still measures (and vice versa, the section never raises)."""
    from corda_tpu.tools import loadtest

    def boom(**kw):
        raise RuntimeError("cluster failed to elect")

    monkeypatch.setattr(loadtest, "run_chaos_loadtest", boom)
    out = bench.bench_durability(n_tx=60, micro_rows=64)
    json.dumps(out)
    assert "RuntimeError" in out["bitrot_chaos"]["error"]
    assert "exactly_once" not in out  # never fabricated from a dead run
    assert out["detect_repair_micro"]["clean_after_repair"] is True
    assert out["repair_s"] > 0.0


def _doctor_report():
    # The minimal bench-report shape the doctor diagnoses: a kernel
    # ceiling, a flagship with low occupancy, and an ingest peak.
    return {
        "metric": "verified_sigs_per_sec", "value": 1200.0,
        "e2e_stream_sigs_per_sec": 100_000.0,
        "kernel_sigs_per_sec": {"4096": 90_000.0},
        "baseline_configs": {
            "raft_validating_3node": {
                "tx_per_sec": 44.0, "p99_ms": 3800.0,
                "loadtest_sigs_per_sec": 2900.0,
                "node_stamps": {
                    "Raft0": {"device_batches": 5, "host_batches": 6}}},
            "ingest_sweep": {"peak_achieved_tx_s": 190.0}},
    }


def test_doctor_section_contract(monkeypatch, tmp_path):
    """The doctor section's one-line-JSON contract (round 17): the
    verdict (roofline + ranked bottlenecks), the normalized trajectory
    record, and the trajectory block (path, delta vs the last record of
    this kind, gate) — serializable, and actually appended to the store
    the env var points at (never the checked-in one from a test)."""
    store = tmp_path / "TRAJECTORY.jsonl"
    monkeypatch.setenv("CORDA_TPU_TRAJECTORY", str(store))
    out = bench.bench_doctor(_doctor_report())

    json.dumps(out)  # the one-line contract: fully serializable
    v = out["verdict"]
    assert v["first_bottleneck"] == "device_occupancy"
    assert v["roofline"]["ceiling_sigs_per_sec"] == 100_000.0
    assert v["roofline"]["gap_factor"] == round(100_000.0 / 2900.0, 2)
    assert v["bottlenecks"][0]["next_experiment"]
    rec = out["record"]
    assert rec["kind"] == "bench_report"
    assert rec["metrics"]["flagship_tx_per_sec"] == 44.0
    assert rec["metrics"]["ingest_peak_achieved_tx_s"] == 190.0
    # First run: appended, no predecessor of this kind to diff against.
    assert out["trajectory"]["appended"] is True
    assert out["trajectory"]["delta"] is None
    assert out["trajectory"]["gate"]["ok"] is True
    assert store.exists()

    # Second run, 25% p99 regression: the delta and the gate both say so
    # in the section — and the run still appends (the gate INFORMS the
    # bench report; perfdoctor --gate is where it blocks).
    worse = _doctor_report()
    worse["baseline_configs"]["raft_validating_3node"]["p99_ms"] = 4750.0
    out2 = bench.bench_doctor(worse)
    json.dumps(out2)
    assert out2["trajectory"]["delta"]["metrics"][
        "flagship_p99_ms"]["change_pct"] == 25.0
    gate = out2["trajectory"]["gate"]
    assert gate["ok"] is False
    assert gate["regressions"][0]["metric"] == "flagship_p99_ms"
    assert out2["trajectory"]["appended"] is True
    assert len(store.read_text().splitlines()) == 2


def test_doctor_section_isolates_store_errors(monkeypatch, tmp_path):
    """An unwritable/corrupt trajectory store costs the trajectory block
    only — the verdict and record still land in the report (the doctor
    section never takes down the one-line contract)."""
    blocker = tmp_path / "occupied"
    blocker.write_text("not json {")
    monkeypatch.setenv("CORDA_TPU_TRAJECTORY", str(blocker))
    out = bench.bench_doctor(_doctor_report())
    json.dumps(out)
    assert out["verdict"]["first_bottleneck"] == "device_occupancy"
    assert out["record"]["kind"] == "bench_report"
    assert out["trajectory"]["appended"] is False
    assert "ValueError" in out["trajectory"]["error"]


def _stub_autotune_baseline(monkeypatch, verdict):
    """Wire bench_autotune to a stubbed baseline sweep (one healthy row
    whose metrics sit exactly on the mock surface's default point) and
    the deterministic monotone mock runner — no real clusters."""
    import types

    from corda_tpu.autotune import controller
    from corda_tpu.tools import loadtest

    fake = types.SimpleNamespace(
        results={2400.0: {"achieved_tx_s": 1000.0, "p99_ms": 50.0,
                          "exactly_once": True}},
        doctor=verdict, first_bottleneck=verdict.get("first_bottleneck"))
    monkeypatch.setattr(loadtest, "run_ingest_sweep", lambda **kw: fake)
    spec = controller.spec_from_verdict(verdict)
    mock = controller.make_mock_runner(spec, "monotone")
    monkeypatch.setattr(controller, "make_ingest_runner",
                        lambda **kw: mock)


def test_autotune_section_contract(monkeypatch, tmp_path):
    """The autotune section's contract (round 21): the loop consumes the
    baseline run's REAL doctor verdict (structured experiment spec, not
    prose), evaluates its gated candidates, reports best vs baseline on
    the swept metric, and appends one ``autotune`` provenance record to
    the store CORDA_TPU_TRAJECTORY points at."""
    from corda_tpu.obs import doctor

    verdict = {"first_bottleneck": "seal",
               "bottlenecks": [{"cause": "seal",
                                "experiment": doctor.suggest_spec("seal")}]}
    _stub_autotune_baseline(monkeypatch, verdict)
    store = tmp_path / "TRAJECTORY.jsonl"
    monkeypatch.setenv("CORDA_TPU_TRAJECTORY", str(store))

    out = bench.bench_autotune(budget=3, seed=7)
    json.dumps(out)  # the one-line contract: fully serializable
    # The sweep came from the verdict's structured experiment, not a
    # fallback: seal implicates the group-commit density levers.
    assert out["experiment_id"] == "raise_group_commit_density"
    assert out["cause"] == "seal"
    assert out["first_bottleneck"] == "seal"
    assert out["knobs"] == ["batch.coalesce_ms", "raft.append_chunk"]
    assert out["candidates_evaluated"] == 3
    # The monotone surface rewards stepping up: the loop must beat the
    # hand-tuned default and commit the winner as a TOML overlay.
    assert out["improved"] is True
    assert out["best_value"] > out["baseline_value"] == 1000.0
    assert out["committed_values"]
    assert "[" in out["committed_overlay"]  # rendered TOML section
    assert len(out["decision_sequence"]) == 3
    assert all(s.endswith(("accept", "reject"))
               for s in out["decision_sequence"])
    # Provenance landed in the env-pointed store, kind "autotune".
    assert out["trajectory"]["appended"] is True
    lines = store.read_text().splitlines()
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["kind"] == "autotune"
    assert rec["autotune"]["experiment_id"] == "raise_group_commit_density"
    assert rec["metrics"]["autotune_best_value"] == out["best_value"]


def test_autotune_section_isolates_store_errors(monkeypatch, tmp_path):
    """An unwritable trajectory store costs the append only — the
    section's sweep results still land (same isolation as the doctor
    section). Unlike bench_doctor, the autotune append never READS the
    store, so the failure mode is a write error, not corrupt JSON."""
    from corda_tpu.obs import doctor

    verdict = {"first_bottleneck": "seal",
               "bottlenecks": [{"cause": "seal",
                                "experiment": doctor.suggest_spec("seal")}]}
    _stub_autotune_baseline(monkeypatch, verdict)
    blocker = tmp_path / "occupied"
    blocker.write_text("i am a file, not a directory")
    monkeypatch.setenv("CORDA_TPU_TRAJECTORY",
                       str(blocker / "TRAJECTORY.jsonl"))

    out = bench.bench_autotune(budget=2, seed=7)
    json.dumps(out)
    assert out["best_value"] >= out["baseline_value"]
    assert out["candidates_evaluated"] == 2
    assert out["trajectory"]["appended"] is False
    assert "Error" in out["trajectory"]["error"]
