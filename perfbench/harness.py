"""The harness: finds a cell's files by name, runs its traffic driver,
reduces what the run recorded to metrics, and prints the result.

Driven by data. BENCHMARK.json names the cell; the cell names its
configuration and its traffic mix; each is a file found by that name:

    perfbench/configs/<config>.json     sizes, guarantees, and the
                                        "driver" that runs them
    perfbench/traffic/<traffic>.json    the mix: loop, rates, sizes
    perfbench/drivers/<driver>.py       run(cell) -> what the run recorded
    perfbench/metrics/<metric>.py       read(run) -> number or None

A later PR adds a cell, a configuration or a metric by adding files and
BENCHMARK.json entries; nothing here changes.

A driver's run() returns a dict with: setup_s, attempted, failed, device
(platform, kind, count, memory_peak_bytes), checks (the numbers that
decide `correct`, each with its limit), trace (lib/trace.reduce of the
traced window, or None), and whatever else its metric readers read.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

PKG = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PKG)
SPEC = os.path.join(ROOT, "BENCHMARK.json")


@dataclass
class Cell:
    """One run of one cell, as its driver sees it."""

    name: str
    chips: int
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t0: float  # time.perf_counter() at process start
    allow_cpu: bool = False  # CPU rehearsal (tests) only
    fault: str | None = None  # rehearsal/control only: break the timed path
    work_dir: str = ""  # this run's scratch, under TMPDIR


def load_spec(path: str = SPEC) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def find_cell(spec: dict, workload: str, traffic_dir: str | None = None):
    """(workload entry, config file, traffic file) for a cell name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(configs[w["config"]]["file"])
    tdir = traffic_dir or os.path.join(PKG, "traffic")
    with open(os.path.join(tdir, w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return w, config, traffic


def _driver(name: str):
    return importlib.import_module(f"perfbench.drivers.{name}")


def _reader(name: str, metrics_dir: str | None = None):
    path = os.path.join(metrics_dir or os.path.join(PKG, "metrics"),
                        name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def metrics_for(spec: dict, workload: str, traced: bool) -> list[dict]:
    """The metrics a cell reports: its end-to-end metrics untraced, its
    per-layer metrics traced (listed for it, or, where a metric lists no
    cells, every cell that reports the end-to-end metric it moves)."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t0: float | None = None, allow_cpu: bool = False,
             fault: str | None = None, spec_path: str = SPEC,
             traffic_dir: str | None = None, metrics_dir: str | None = None,
             overrides: dict | None = None) -> dict:
    """One run: the result object the command prints last. The keyword
    arguments after `trace` are the rehearsal's and the control's entry
    (tests, perfbench/controls.py), never the command's."""
    spec = load_spec(spec_path)
    w, config, traffic = find_cell(spec, workload, traffic_dir)
    traffic = {**traffic, **(overrides or {})}
    work_dir = tempfile.mkdtemp(prefix="perfbench-")
    cell = Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, seed=int(seed), seconds=float(seconds),
                trace=bool(trace), t0=time.perf_counter() if t0 is None
                else t0, allow_cpu=allow_cpu, fault=fault, work_dir=work_dir)
    try:
        run = _driver(config["driver"]).run(cell)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    metrics = {}
    for m in metrics_for(spec, workload, trace):
        value = _reader(m["name"], metrics_dir)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {c["name"]: {"value": c["value"], "limit": c["limit"]}
              for c in run["checks"]}
    correct = bool(checks) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    device = dict(run["device"])
    result = {"correct": correct, "attempted": int(run["attempted"]),
              "failed": int(run["failed"]), "metrics": metrics,
              "device": device}
    red = run.get("trace")
    if trace:
        device["busy_s"] = red["busy_s"] if red else 0.0
        device["window_s"] = red["window_s"] if red else run["window_s"]
        if red:
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv, t0: float) -> int:
    parser = argparse.ArgumentParser(prog="perfbench/run.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    from perfbench.lib.device import NoAccelerator

    # Set-up counts from process start, interpreter start-up included.
    t0 -= _process_age_s() - (time.perf_counter() - t0)
    # JAX's persistent compile cache lives inside this checkout, at a fixed
    # path (the path is part of the cache key); the program takes it from
    # this variable, and so do the processes it starts.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0=t0)
    except NoAccelerator as e:
        print(f"perfbench: {e}", file=sys.stderr, flush=True)
        return 2
    emit(result)
    return 0


def emit(result: dict) -> None:
    """The run's last lines: each compared number beside its limit on
    standard error, then the result object as standard output's last line."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def _process_age_s() -> float:
    """Seconds this process has lived (Linux /proc; 0 elsewhere)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0
