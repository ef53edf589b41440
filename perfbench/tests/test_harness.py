"""CPU rehearsal of the benchmark: every cell at a tiny size through the
harness's internal entry (harness.run_cell with allow_cpu), the faults the
cells can have, a cell and a metric added by files alone, the trace
reduction on a recorded trace, and the counts kept with the benchmark.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

from __future__ import annotations

import gzip
import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout, redirect_stderr

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402
from perfbench.lib import ed25519_ref, kernel_ops, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY_BULK = {"batch_sigs": 64, "batches": 2, "signer_pool": 16,
             "damage_every": 8, "malformed": 3, "reference_sample": 16}
BIG_SEED = 2 ** 31 + 12345


@pytest.fixture(autouse=True)
def _cpu(monkeypatch):
    # The 64-lane batches take the device path (the XLA graph on the CPU).
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("CORDA_TPU_DEVICE_MIN_SIGS", "0")


def _emitted(result: dict) -> dict:
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        harness.emit(result)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in last
    assert list(last)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith("check ")
    return last


def test_bulk_cell_rehearsal():
    r = _emitted(harness.run_cell("bulk_verify.100k", BIG_SEED, 1.0, False,
                                  allow_cpu=True, overrides=TINY_BULK))
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"verified_sigs_per_s", "setup_s"}
    assert r["checks"]["lanes_wrong"] == {"value": 0, "limit": 0}
    assert r["device"]["platform"] == "cpu"


def test_bulk_cell_traced_rehearsal_reports_device_fields():
    r = _emitted(harness.run_cell("bulk_verify.100k", 5, 1.0, True,
                                  allow_cpu=True, overrides=TINY_BULK))
    assert r["correct"] is True
    assert "busy_s" in r["device"] and "window_s" in r["device"]
    # No end-to-end metric in a traced run.
    assert not set(r["metrics"]) & {"verified_sigs_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["control", "flip", "half", "stale"])
def test_bulk_faults_fail_correct(fault):
    r = harness.run_cell("bulk_verify.100k", 77, 1.0, False, allow_cpu=True,
                         fault=fault, overrides=TINY_BULK)
    assert r["correct"] is False
    assert r["checks"]["lanes_wrong"]["value"] > 0


def test_corpus_is_signed_without_the_program():
    """The corpus and its verdicts come from OpenSSL: make_batch runs in a
    process where the program cannot be imported."""
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "sys.modules['corda_tpu'] = None; "
            "from perfbench.drivers import bulk_verify as b; "
            "import numpy as np, json; "
            "t = json.loads(sys.argv[2]); x = b.make_batch(2 ** 40 + 3, 1, t); "
            "print(int(np.unpackbits(np.frombuffer(x['truth'], np.uint8))"
            "[:x['n']].sum()))")
    p = subprocess.run([sys.executable, "-c", code, ROOT,
                        json.dumps(TINY_BULK)], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    # 64 lanes: 8 damaged, 3 malformed (one may fall on a damaged lane)
    assert 53 <= int(p.stdout) <= 56


def test_corpus_refuses_a_verdict_openssl_does_not_confirm(monkeypatch):
    from perfbench.drivers import bulk_verify

    monkeypatch.setattr(bulk_verify, "_openssl_verify", lambda *a: True)
    with pytest.raises(RuntimeError, match="OpenSSL disagrees"):
        bulk_verify.make_batch(7, 0, TINY_BULK)


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      harness.load_spec()["workloads"]])
def test_command_without_accelerator_exits_nonzero(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_command_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "bulk_verify.100k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    """A later PR's cell: a new traffic file, a new metric reader and new
    BENCHMARK.json entries; no harness code changes."""
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["workloads"].append({
        "name": "bulk_verify.extra", "config": "bulk_verify_1chip",
        "traffic": "bulk_extra", "chips": 1, "why": "added by files"})
    spec["per_layer"].append({
        "name": "calls.extra", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "provider + host packing",
        "moves": "verified_sigs_per_s", "workloads": ["bulk_verify.extra"]})
    for m in spec["end_to_end"]:
        if m["name"] == "verified_sigs_per_s":
            m["workloads"].append("bulk_verify.extra")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    tdir, mdir = tmp_path / "traffic", tmp_path / "metrics"
    shutil.copytree(os.path.join(ROOT, "perfbench", "traffic"), tdir)
    shutil.copytree(os.path.join(ROOT, "perfbench", "metrics"), mdir)
    extra = json.load(open(tdir / "bulk_100k.json"))
    extra.update(TINY_BULK, batch_sigs=96)
    (tdir / "bulk_extra.json").write_text(json.dumps(extra))
    (mdir / "calls.extra.py").write_text(
        "def read(run):\n    return run.get('calls')\n")
    kw = dict(allow_cpu=True, spec_path=str(tmp_path / "BENCHMARK.json"),
              traffic_dir=str(tdir), metrics_dir=str(mdir))
    r = harness.run_cell("bulk_verify.extra", 3, 1.0, False, **kw)
    assert r["correct"] and r["attempted"] % 96 == 0
    assert "verified_sigs_per_s" in r["metrics"]
    r = harness.run_cell("bulk_verify.extra", 4, 1.0, True, **kw)
    assert r["metrics"]["calls.extra"]["value"] >= 1


def _brute_busy(events, lo, hi):
    """Busy time by marking every nanosecond bucket of 1 us."""
    import numpy as np

    step = 100  # ns
    lo, hi = int(lo), int(hi)
    mark = np.zeros((hi - lo) // step + 1, bool)
    for _, s, d in events:
        a, b = int(max(s, lo)), int(min(s + d, hi))
        if b > a:
            mark[(a - lo) // step:(b - lo + step - 1) // step] = True
    return int(mark.sum()) * step


def test_trace_reduce_synthetic():
    host = [["python", trace.WINDOW_SPAN, 1000, 9000]]  # window [1000, 10000)
    ops = [["a", 0, 2000], ["b", 1500, 1000], ["c", 4000, 1000],
           ["k", 7000, 2000], ["k", 9500, 2000]]
    mods = [["jit_kernel_x(1)", 7000, 2000], ["jit_kernel_x(1)", 9500, 2000]]
    red = trace.reduce({"devices": {"/device:TPU:0": {
        trace.OPS_LINE: ops, trace.MODULES_LINE: mods}}, "host": host},
        {"kern": "kernel_x"})
    assert red["window_s"] == pytest.approx(9e-6)
    # busy: [1000,2500) + [4000,5000) + [7000,9000) + [9500,10000)
    assert red["busy_s"] == pytest.approx(5e-6)
    assert red["kernel_s"]["kern"] == pytest.approx(2.5e-6)
    assert [round(g[1] * 1e9) for g in red["idle_gaps"]] == [2000, 1500, 500]


def test_trace_reduce_recorded_tpu_trace():
    path = os.path.join(DATA, "bulk_trace_tpu.json.gz")
    with gzip.open(path, "rt") as f:
        collected = json.load(f)
    red = trace.reduce(collected, {"verify": "verify_arrays_pallas",
                                   "challenge": "challenge_words"})
    lo, hi = trace.window_of(collected)
    (plane, lines), = collected["devices"].items()
    want = _brute_busy(lines[trace.OPS_LINE], lo, hi)
    assert red["busy_s"] == pytest.approx(want / 1e9, rel=2e-3)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["kernel_s"]["verify"] > red["kernel_s"]["challenge"] > 0
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_field_mul_count_matches_the_kernel(monkeypatch):
    """kernel_ops' count against the multiplications a trace of the
    program's verify_core performs, with its loops unrolled."""
    import jax
    import jax.numpy as jnp

    from corda_tpu.ops import ed25519_jax as ej
    from corda_tpu.ops import fe25519 as fe

    calls = {"n": 0}

    def counting_mul(a, b):  # counts; the limb convolution is not traced
        calls["n"] += 1
        return a + b

    def python_fori(lo, hi, body, init):
        for i in range(lo, hi):
            init = body(i, init)
        return init

    monkeypatch.setattr(fe, "mul", counting_mul)
    monkeypatch.setattr(fe.jax.lax, "fori_loop", python_fori)
    n = 1
    shape = jax.ShapeDtypeStruct
    jax.eval_shape(
        lambda y, s, r, rs, sn, hn: ej.verify_core(y, s, r, rs, sn, hn,
                                                    unroll=True),
        shape((20, n), jnp.int32), shape((n,), jnp.int32),
        shape((20, n), jnp.int32), shape((n,), jnp.int32),
        shape((64, n), jnp.int32), shape((64, n), jnp.int32))
    assert calls["n"] == kernel_ops.field_muls_per_sig()


def test_reference_agrees_with_the_oracle_on_edge_cases():
    from corda_tpu.crypto import ref_ed25519 as oracle

    import numpy as np

    rng = np.random.default_rng(5)
    L = ed25519_ref.L
    for _ in range(12):
        seed, m = rng.bytes(32), rng.bytes(32)
        pk, sig = oracle.public_key(seed), oracle.sign(seed, m)
        s_plus_l = sig[:32] + (int.from_bytes(sig[32:], "little")
                               + L).to_bytes(32, "little")
        for case in [(pk, m, sig), (pk, m[:-1] + b"\0", sig),
                     (pk, m, sig[:3] + bytes([sig[3] ^ 16]) + sig[4:]),
                     (b"\xff" * 32, m, sig), (pk[:31], m, sig),
                     (pk, m, sig + b"\0"), (pk, m, s_plus_l),
                     (rng.bytes(32), m, sig)]:
            assert ed25519_ref.verify(*case) == oracle.verify(*case)


def test_benchmark_json_names_existing_files():
    spec = harness.load_spec()
    pkg = os.path.join(ROOT, "perfbench")
    for c in spec["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert os.path.exists(os.path.join(pkg, "drivers",
                                           cfg["driver"] + ".py"))
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(pkg, "traffic",
                                           w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
        names = {m["name"] for m in harness.metrics_for(spec, w["name"],
                                                        False)}
        assert "setup_s" in names and len(names) >= 2
        assert harness.metrics_for(spec, w["name"], True)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(pkg, "metrics",
                                           m["name"] + ".py"))
    for m in spec["per_layer"]:
        for w in m.get("workloads", []):
            assert w in e2e[m["moves"]].get("workloads", [w])


@pytest.mark.skipif(not os.environ.get("PERFBENCH_INTERPRET"),
                    reason="the Pallas kernel in interpret mode takes ~6 "
                           "minutes on this CPU; set PERFBENCH_INTERPRET=1")
def test_bulk_cell_through_the_pallas_kernel_in_interpret_mode(monkeypatch):
    """The bulk cell's timed path with the Pallas kernel itself (1,024-lane
    bucket) run by the Pallas interpreter instead of the XLA graph the CPU
    takes by default."""
    import functools

    from corda_tpu.ops import ed25519_jax as ej
    from corda_tpu.ops import ed25519_pallas as ep

    monkeypatch.setitem(ej._PALLAS_STATE, "available", True)
    monkeypatch.setattr(ep, "verify_arrays_pallas", functools.partial(
        ep.verify_arrays_pallas, interpret=True))
    r = harness.run_cell("bulk_verify.100k", 9, 1.0, False, allow_cpu=True,
                         overrides=TINY_BULK)
    assert r["correct"] is True
    assert ej.last_backend() == "pallas"
