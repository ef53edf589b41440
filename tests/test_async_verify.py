"""The async verify pipeline (crypto/async_verify.py) + its node wiring.

Covers the ISSUE acceptance list: submit/complete ordering, bounded
in-flight depth, feeder-exception propagation (a failed batch REJECTS its
flows instead of hanging them), kill-during-in-flight restore (the
at-least-once replay contract when results die with the process), the
sync fallback behind batch.async_verify = false, adaptive-crossover
bounds, and the CI smoke that runs a miniature loadtest through the
bench one-line JSON contract with the pipeline on.
"""

import json
import threading
import time

import numpy as np
import pytest

from corda_tpu.crypto.async_verify import (
    AdaptiveCrossover,
    AsyncVerifyService,
    VerifyBatchHandle,
)
from corda_tpu.crypto.keys import KeyPair, SignatureError
from corda_tpu.crypto.provider import VerifyJob
from corda_tpu.flows.api import FlowLogic, VerifySigRequest, register_flow
from corda_tpu.node.config import BatchConfig, NodeConfig
from corda_tpu.node.node import Node


# ---------------------------------------------------------------------------
# Stub verifiers (service-level tests: no node, no kernel)
# ---------------------------------------------------------------------------


class _OkVerifier:
    name = "stub-ok"

    def __init__(self):
        self.calls = 0

    def verify_batch(self, jobs):
        self.calls += 1
        return [True] * len(jobs)


class _BlockingVerifier:
    """Holds every verify_batch until released — models a device mid-kernel."""

    name = "stub-blocking"

    def __init__(self):
        self.entered = threading.Event()
        self.release = threading.Event()

    def verify_batch(self, jobs):
        self.entered.set()
        assert self.release.wait(30.0), "test forgot to release the verifier"
        return [True] * len(jobs)


class _RaisingVerifier:
    name = "stub-raising"

    def verify_batch(self, jobs):
        raise RuntimeError("device fell off the bus")


def _jobs(n):
    return [VerifyJob(pubkey=b"\x00" * 32, message=b"\x01" * 32,
                      sig=b"\x02" * 64) for _ in range(n)]


def _drain_until(svc, want, timeout=10.0):
    """Drain handles off the completion queue until `want` arrived."""
    done = []
    deadline = time.monotonic() + timeout
    while len(done) < want and time.monotonic() < deadline:
        done.extend(svc.drain())
        time.sleep(0.002)
    assert len(done) == want, f"only {len(done)}/{want} batches completed"
    return done


# ---------------------------------------------------------------------------
# Service-level: ordering, depth, failure, close
# ---------------------------------------------------------------------------


def test_submit_drain_ordering_and_stats():
    svc = AsyncVerifyService(_OkVerifier(), depth=4)
    try:
        handles = [svc.submit(_jobs(i + 1), context=f"batch-{i}")
                   for i in range(3)]
        assert svc.in_flight == 3
        done = _drain_until(svc, 3)
        # FIFO through the single feeder: completion preserves submit order.
        assert [h.context for h in done] == ["batch-0", "batch-1", "batch-2"]
        assert done is not handles  # drain returns the same handle objects
        assert all(a is b for a, b in zip(done, handles))
        for i, h in enumerate(done):
            assert h.ok == [True] * (i + 1)
            assert h.error is None
            assert h.tier == "host"  # stub has no device_batches counter
            assert h.finished_at >= h.started_at >= 0
        assert svc.in_flight == 0
        stats = svc.stats()
        assert stats["submitted_batches"] == stats["completed_batches"] == 3
        assert stats["submitted_sigs"] == stats["completed_sigs"] == 6
        assert stats["failed_batches"] == 0
        assert stats["verify_wall_s"] >= 0.0
    finally:
        assert svc.close()


def test_bounded_depth_backpressure():
    stub = _BlockingVerifier()
    svc = AsyncVerifyService(stub, depth=2)
    try:
        svc.submit(_jobs(1), context=0)
        assert svc.can_submit()  # one slot left
        svc.submit(_jobs(1), context=1)
        assert not svc.can_submit()  # pipeline full: loop must accumulate
        assert svc.in_flight == 2
        stub.release.set()
        _drain_until(svc, 2)
        assert svc.can_submit()
        assert svc.in_flight == 0
    finally:
        stub.release.set()
        assert svc.close()


def test_feeder_exception_lands_in_handle_not_thread_death():
    svc = AsyncVerifyService(_RaisingVerifier(), depth=2)
    try:
        svc.submit(_jobs(2), context="doomed")
        (handle,) = _drain_until(svc, 1)
        assert handle.ok is None
        assert "fell off the bus" in str(handle.error)
        assert svc.stats()["failed_batches"] == 1
        # The feeder survived the exception: the next submit still works.
        svc.verifier = _OkVerifier()
        svc.submit(_jobs(1), context="after")
        (h2,) = _drain_until(svc, 1)
        assert h2.error is None and h2.ok == [True]
    finally:
        assert svc.close()


def test_close_rejects_submit_and_bounds_the_join():
    stub = _BlockingVerifier()
    svc = AsyncVerifyService(stub, depth=1)
    svc.submit(_jobs(1), context=0)
    assert stub.entered.wait(10.0)
    # Feeder is wedged inside verify_batch: close must give up on time.
    assert svc.close(timeout=0.2) is False
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(_jobs(1), context=1)
    stub.release.set()
    assert svc.close(timeout=10.0) is True


def test_depth_must_be_positive():
    with pytest.raises(ValueError, match="depth"):
        AsyncVerifyService(_OkVerifier(), depth=0)


# ---------------------------------------------------------------------------
# target_sigs: the accumulate-across-rounds gate
# ---------------------------------------------------------------------------


class _DeviceishVerifier(_OkVerifier):
    def __init__(self, min_sigs=512, ready=True):
        super().__init__()
        self.device_min_sigs = min_sigs
        self.device_gate = threading.Event()
        if ready:
            self.device_gate.set()
        self.device_batches = 0


def test_target_sigs_tracks_crossover_and_gate():
    # Host-only verifier: classic max_sigs flush policy.
    svc = AsyncVerifyService(_OkVerifier(), adaptive=False)
    assert svc.target_sigs(4096) == 4096
    # Warm device: accumulate to the crossover, not to max_sigs.
    svc = AsyncVerifyService(_DeviceishVerifier(min_sigs=512))
    assert svc.target_sigs(4096) == 512
    assert svc.target_sigs(256) == 256  # never above the batch cap
    # Cold device: batches host-route anyway, so don't starve the host tier.
    svc = AsyncVerifyService(_DeviceishVerifier(min_sigs=512, ready=False))
    assert svc.target_sigs(4096) == 4096


# ---------------------------------------------------------------------------
# AdaptiveCrossover
# ---------------------------------------------------------------------------


def _handle(n, wall_s, tier):
    h = VerifyBatchHandle(_jobs(n), context=None)
    h.started_at = 100.0
    h.finished_at = 100.0 + wall_s
    h.ok = [True] * n
    h.tier = tier
    return h


def test_adaptive_lowers_crossover_when_device_wins():
    v = _DeviceishVerifier(min_sigs=512)
    ac = AdaptiveCrossover(v)
    assert ac.enabled and ac.effective_min_sigs == 512
    # Evidence on one tier only: static policy holds.
    ac.observe(_handle(512, 0.001, "device"))
    assert v.device_min_sigs == 512
    # Device 10x faster than host: crossover walks down, bounded by FLOOR.
    for _ in range(40):
        ac.observe(_handle(512, 0.001, "device"))
        ac.observe(_handle(512, 0.010, "host"))
    assert v.device_min_sigs == AdaptiveCrossover.FLOOR
    assert ac.adjustments > 0


def test_adaptive_raises_crossover_when_host_wins_bounded():
    v = _DeviceishVerifier(min_sigs=512)
    ac = AdaptiveCrossover(v)
    for _ in range(40):
        ac.observe(_handle(512, 0.010, "device"))
        ac.observe(_handle(512, 0.001, "host"))
    assert v.device_min_sigs == ac.ceiling  # stops at the ceiling
    assert ac.ceiling >= 8 * 512


def test_adaptive_ignores_noise_samples():
    v = _DeviceishVerifier(min_sigs=512)
    ac = AdaptiveCrossover(v)
    ac.observe(_handle(8, 0.001, "device"))  # below MIN_SAMPLE_SIGS
    bad = _handle(512, 0.001, "device")
    bad.error = RuntimeError("boom")
    ac.observe(bad)  # errored batches measure nothing
    assert ac.device_rate == 0.0
    assert v.device_min_sigs == 512


def test_adaptive_disabled_for_host_only_verifier():
    ac = AdaptiveCrossover(_OkVerifier())
    assert not ac.enabled
    ac.observe(_handle(512, 0.001, "device"))
    assert ac.effective_min_sigs is None


# ---------------------------------------------------------------------------
# Node-level: flows through the pipeline, sync fallback, kill/restore
# ---------------------------------------------------------------------------


@register_flow
class SigCheckFlow(FlowLogic):
    """Parks on the verify pump for one raw signature (checkpointable
    primitives only: the kill/restore test rebuilds it from disk)."""

    def __init__(self, pubkey: bytes, message: bytes, sig_bytes: bytes):
        self.pubkey = pubkey
        self.message = message
        self.sig_bytes = sig_bytes

    def call(self):
        yield VerifySigRequest(self.pubkey, self.message, self.sig_bytes,
                               description="SigCheckFlow")
        return "verified"


def _make_node(tmp_path, name="AsyncNode", **batch_kw):
    return Node(NodeConfig(
        name=name,
        base_dir=tmp_path / name,
        network_map=tmp_path / "netmap.json",
        batch=BatchConfig(max_wait_ms=0.5, **batch_kw),
    )).start()


def _sig_args(seed=b"\x07" * 32, message=b"async-verify-me".ljust(32, b".")):
    kp = KeyPair.generate(seed)
    sig = kp.sign(message)
    return bytes(sig.by.encoded), bytes(message), bytes(sig.bytes)


def _pump(node, predicate, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        node.run_once(timeout=0.01)
        if predicate():
            return
    raise AssertionError("node did not settle in time")


def test_async_node_verifies_and_rejects(tmp_path):
    node = _make_node(tmp_path)
    try:
        assert node.smm.async_verify is not None
        pk, msg, sig = _sig_args()
        good = node.start_flow(SigCheckFlow(pk, msg, sig))
        bad_sig = bytes([sig[0] ^ 1]) + sig[1:]
        bad = node.start_flow(SigCheckFlow(pk, msg, bad_sig))
        _pump(node, lambda: good.result.done and bad.result.done)
        assert good.result.result() == "verified"
        with pytest.raises(SignatureError):
            bad.result.result()
        stats = node.smm.async_verify.stats()
        assert stats["completed_batches"] >= 1
        assert stats["completed_sigs"] >= 2
        assert stats["in_flight"] == 0
    finally:
        node.stop()


def test_sync_mode_disables_pipeline(tmp_path):
    node = _make_node(tmp_path, name="SyncNode", async_verify=False)
    try:
        assert node.smm.async_verify is None
        pk, msg, sig = _sig_args()
        h = node.start_flow(SigCheckFlow(pk, msg, sig))
        _pump(node, lambda: h.result.done)
        assert h.result.result() == "verified"
        assert node.smm.metrics["verify_batches"] >= 1
    finally:
        node.stop()


def test_feeder_failure_rejects_flows_not_hangs(tmp_path):
    node = _make_node(tmp_path, name="FailNode")
    try:
        # Swap the verifier under the service BEFORE the lazy feeder spawns:
        # every batch now raises inside the feeder thread.
        node.smm.async_verify.verifier = _RaisingVerifier()
        pk, msg, sig = _sig_args()
        h = node.start_flow(SigCheckFlow(pk, msg, sig))
        _pump(node, lambda: h.result.done)
        # Unregistered exception types rebuild as FlowException through the
        # checkpoint-exception codec; the message survives verbatim.
        with pytest.raises(Exception, match="fell off the bus"):
            h.result.result()
        assert node.smm.async_verify.stats()["failed_batches"] == 1
        assert node.smm.in_flight_count == 0  # rejected, not parked forever
    finally:
        node.stop()


def test_kill_during_inflight_replays_at_least_once(tmp_path):
    """Results lost with the process cost a re-verify, never a lost flow:
    the park wrote no outcome, so the reborn node replays the flow and it
    re-yields the verify (the existing at-least-once contract)."""
    node = _make_node(tmp_path, name="Phoenix")
    stub = _BlockingVerifier()
    node.smm.async_verify.verifier = stub
    pk, msg, sig = _sig_args()
    node.start_flow(SigCheckFlow(pk, msg, sig))
    # Round the batch into the feeder and wedge it mid-verify.
    _pump(node, lambda: stub.entered.is_set())
    assert node.smm.async_verify.in_flight == 1
    # "Crash": the completed handle is never drained — its result dies
    # with this node object. Release first so close() can join the feeder.
    stub.release.set()
    node.stop()
    del node

    reborn = Node(NodeConfig(
        name="Phoenix",
        base_dir=tmp_path / "Phoenix",
        network_map=tmp_path / "netmap.json",
        batch=BatchConfig(max_wait_ms=0.5),
    )).start()
    try:
        assert reborn.smm.in_flight_count == 1  # checkpoint survived
        _pump(reborn, lambda: reborn.smm.in_flight_count == 0)
        assert reborn.smm.metrics["finished"] == 1
        assert reborn.smm.metrics["verify_sigs"] >= 1  # re-verified for real
    finally:
        reborn.stop()


def test_node_metrics_exposes_pipeline_stats(tmp_path):
    from corda_tpu.node.rpc import NodeRpcOps

    node = _make_node(tmp_path, name="MetricsNode")
    try:
        pk, msg, sig = _sig_args()
        h = node.start_flow(SigCheckFlow(pk, msg, sig))
        _pump(node, lambda: h.result.done)
        m = NodeRpcOps(node).node_metrics()
        av = m["async_verify"]
        assert av["depth"] == 2
        assert av["completed_batches"] >= 1
        assert "verify_drain" in m["round_stage_s"]
        assert "verify_submit" in m["round_stage_s"]
    finally:
        node.stop()

    sync_node = _make_node(tmp_path, name="MetricsSync", async_verify=False)
    try:
        assert NodeRpcOps(sync_node).node_metrics()["async_verify"] is None
    finally:
        sync_node.stop()


# ---------------------------------------------------------------------------
# CI smoke (ISSUE satellite 6): a miniature loadtest with the pipeline on,
# reported through the bench one-line JSON contract.
# ---------------------------------------------------------------------------


def test_bench_contract_smoke_with_async_loadtest(monkeypatch, capsys):
    import bench
    from test_bench_report import _stub_phases

    from corda_tpu.tools.loadtest import run_loadtest

    def mini_cluster(**kw):
        res = run_loadtest(n_tx=6, notary="validating", max_seconds=60.0,
                           batch=BatchConfig(max_wait_ms=0.5))
        return {"tx_committed": res.tx_committed,
                "tx_per_sec": res.tx_per_sec,
                "verify_batches": res.verify_batches}

    _stub_phases(monkeypatch)
    monkeypatch.setattr(bench, "bench_raft_cluster", mini_cluster)
    assert bench.main() == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1  # the one-line driver contract
    report = json.loads(out[0])
    assert report["metric"] == "verified_sigs_per_sec"
    cluster = report["baseline_configs"]["raft_notary_3node"]
    assert cluster["tx_committed"] == 6  # real flows really notarised
    assert cluster["verify_batches"] >= 1
