"""chip_smoke.py rehearsed on the CPU at a tiny size.

The phase functions run in this process with the TPU check stubbed here
(the program has no option for it); the whole script, run as the driver
runs it, must fail on a host without a TPU."""

import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tiny(monkeypatch):
    # 64 lanes: the XLA graph verifies ~15 sigs/s on this CPU. The size
    # crossover would host-route such a batch, so it is lowered here.
    monkeypatch.setattr(chip_smoke, "N_LANES", 64)
    monkeypatch.setattr(chip_smoke, "ORACLE_SAMPLE", 16)
    # The padded call: 40 live lanes in the same 64-lane bucket.
    monkeypatch.setattr(chip_smoke, "PADDED_LIVE", 40)
    monkeypatch.setattr(chip_smoke, "PADDED_BUCKET", 64)
    monkeypatch.setenv("CORDA_TPU_DEVICE_MIN_SIGS", "0")
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda n: {
        "platform": "cpu", "kind": "cpu", "count": n})


def test_kernel_phase_rehearsal_agrees_with_host_and_oracle(tiny):
    refs = chip_smoke.host_answers(7)
    assert refs["host_rejects"] >= 64 // chip_smoke.CORRUPT_EVERY
    res = chip_smoke.kernel_phase(lambda msg: None, 7)
    provider = chip_smoke._unpack(res["provider_ok"])
    hashed = chip_smoke._unpack(res["hashed_ok"])
    assert chip_smoke.check_lanes("provider", provider, refs)["lanes"] == 64
    assert chip_smoke.check_lanes("hashed", hashed, refs)["agree_oracle"] == 16
    # CPU backend: the XLA graph (the chip run requires "pallas").
    assert res["provider_backend"] == res["hashed_backend"] == "xla"
    assert res["provider_batches"] == {"device": 2, "host": 0}
    assert res["pallas_failures_total"] == 0 and res["degraded"] == 0
    padded = chip_smoke.check_padded("padded", res["padded_ok"], refs)
    assert padded["live"] == 40 and padded["accepted"] > 0
    assert res["padded_backend"] == "xla"
    assert res["padded_blocks_skipped"] == 0  # the XLA graph has no blocks
    # A lane flipped against the host answer is caught.
    provider[5] = not provider[5]
    with pytest.raises(chip_smoke.SmokeFailure, match="disagree"):
        chip_smoke.check_lanes("provider", provider, refs)
    # So is a dead lane of the padded call that reads accept.
    bits = np.unpackbits(np.frombuffer(res["padded_ok"], np.uint8))
    bits[50] = 1
    with pytest.raises(chip_smoke.SmokeFailure, match="dead lanes"):
        chip_smoke.check_padded("padded", np.packbits(bits).tobytes(), refs)


def test_mesh_phase_rehearsal_shards_evenly(tiny):
    res = chip_smoke.mesh_phase(lambda msg: None, 3, n_devices=4)
    single = chip_smoke._unpack(res["single_ok"])
    mesh = chip_smoke._unpack(res["mesh_ok"])
    assert np.array_equal(single, mesh)
    assert res["sidecar"]["per_device_batch_sigs_hist"] == {"16": 2}
    assert res["sidecar"]["device_batches"] == 2
    assert res["client_fallbacks"] == 0
    assert len({s["device"] for s in res["shards"]}) == 4
    assert all(s["lanes"] == 16 for s in res["shards"])


def _fake_loadtest(**over):
    stamp = {"sidecar": {"fallbacks": 0, "degraded": 0, "sigs": 64},
             "host_batches": 0}
    base = dict(tx_committed=1000, tx_requested=1000, tx_rejected=0,
                exactly_once=True, ledger_committed=1000, tx_per_sec=50.0,
                sigs_per_sec=1600.0, p50_ms=100.0, p99_ms=300.0,
                device_warm_wait_s=1.0,
                sidecar={"device_ready": True, "warm_error": None,
                         "kernel_backend": "pallas", "device_batches": 3,
                         "host_batches": 9, "errors": 0},
                node_stamps={"Raft0": stamp, "Raft1": stamp, "Raft2": stamp})
    base.update(over)
    return SimpleNamespace(**base)


def test_notary_phase_checks(monkeypatch):
    from corda_tpu.tools import loadtest

    calls = []

    def run(**kw):
        calls.append(kw)
        if kw["clients"] == 2:  # the default load misses the crossover
            return _fake_loadtest(sidecar={
                "device_ready": True, "warm_error": None,
                "kernel_backend": None, "device_batches": 0,
                "host_batches": 12, "errors": 0})
        return _fake_loadtest()

    monkeypatch.setattr(loadtest, "run_loadtest_multiprocess", run)
    out = chip_smoke.notary_phase()
    assert [c["clients"] for c in calls] == [2, 4]
    assert all(c["notary_device"] == "accelerator" and c["sidecar"]
               for c in calls)
    assert len(out["attempts"]) == 2

    bad_member = {"sidecar": {"fallbacks": 1, "degraded": 1, "sigs": 0},
                  "host_batches": 4}
    for broken in ({"tx_committed": 999},
                   {"exactly_once": False},
                   {"node_stamps": {"Raft0": bad_member}}):
        monkeypatch.setattr(loadtest, "run_loadtest_multiprocess",
                            lambda b=broken, **kw: _fake_loadtest(**b))
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.notary_phase()


def test_smoke_fails_without_a_tpu(tmp_path):
    # As the driver runs it, from the checkout root, on a host whose JAX
    # finds no TPU: non-zero, and no result line.
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr
