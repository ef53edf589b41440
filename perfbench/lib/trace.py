"""From a JAX profiler trace to the device numbers the benchmark reports.

`collect` reads the `.xplane.pb` that `jax.profiler` wrote (in the
process that held the chip) into plain lists. `reduce` turns those into
busy and idle time, kernel time by module name, the ten device
operations that took most time, and the ten longest idle gaps, each
named by what the host was doing in it. Only `reduce` does arithmetic,
and it is tested on a small recorded trace (tests/data).

The window is the host span named WINDOW_SPAN, which the benchmark opens
when its measured window starts and closes when it ends, so device and
window share the trace's own clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "perfbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start(trace_dir: str) -> None:
    """Start the profiler without its Python tracer, which records every
    Python call and slowed the bulk window by a third (my chip run, PR 22)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def collect(trace_dir: str, cpu_ops: bool = False) -> dict:
    """Planes of the newest trace under `trace_dir`. `cpu_ops` (the CPU
    rehearsal only) takes XLA's CPU thunks as the device's operations."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            devices[plane.name] = {
                line.name: [[ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:CPU"):
            cpu = []
            for line in plane.lines:
                for ev in line.events:
                    host.append([line.name, ev.name, ev.start_ns,
                                 ev.duration_ns])
                    if cpu_ops:
                        stats = dict(ev.stats)
                        if "hlo_module" in stats:
                            cpu.append([ev.name, ev.start_ns, ev.duration_ns,
                                        stats["hlo_module"]])
            if cpu_ops:
                devices["/device:CPU-rehearsal"] = {
                    OPS_LINE: [e[:3] for e in cpu],
                    MODULES_LINE: [[f"{m}", s, d] for _, s, d, m in cpu]}
    return {"devices": devices, "host": host}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def window_of(collected: dict):
    """[start_ns, end_ns] of the benchmark's window span, or None."""
    spans = [(s, s + d) for _, name, s, d in collected["host"]
             if name == WINDOW_SPAN]
    return list(max(spans, key=lambda x: x[1] - x[0])) if spans else None


def reduce(collected: dict, kernels: dict | None = None) -> dict | None:
    """Busy/idle, kernel time and the breakdown over the window.

    kernels: {label: regex}; a kernel's time is the summed duration of the
    XLA Modules events (falling back to XLA Ops) whose names match.
    Returns None when the trace holds no device operation in the window:
    a reader then finds nothing to read.
    """
    win = window_of(collected)
    if win is None:
        return None
    lo, hi = win
    per_device, ops_time, busy_all = [], {}, []
    kernel_ns = {k: 0 for k in (kernels or {})}
    for plane, lines in sorted(collected["devices"].items()):
        ops = lines.get(OPS_LINE)
        if not ops:
            continue
        ivals = _clip([[s, s + d] for _, s, d in ops], lo, hi)
        busy = _union(ivals)
        busy_ns = sum(e - s for s, e in busy)
        per_device.append(busy_ns)
        busy_all.extend(busy)
        mods = lines.get(MODULES_LINE) or ops
        by_start = sorted(mods, key=lambda m: m[1])
        starts = [m[1] for m in by_start]
        for name, s, d in ops:
            inside = min(s + d, hi) - max(s, lo)
            if inside > 0:
                label = _op_label(name, s, by_start, starts)
                ops_time[label] = ops_time.get(label, 0) + inside
        for label, pattern in (kernels or {}).items():
            rx = re.compile(pattern)
            for name, s, d in mods:
                inside = min(s + d, hi) - max(s, lo)
                if inside > 0 and rx.search(name):
                    kernel_ns[label] += inside
    if not per_device or sum(per_device) == 0:
        return None
    n_dev = len(per_device)
    gaps = []
    edge = lo
    for s, e in _union(busy_all) + [[hi, hi]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(per_device) / n_dev / 1e9,
        "devices": n_dev,
        "kernel_s": {k: v / n_dev / 1e9 for k, v in kernel_ns.items()},
        "device_ops": [[n, t / n_dev / 1e9] for n, t in sorted(
            ops_time.items(), key=lambda kv: kv[1], reverse=True)[:10]],
        "idle_gaps": [[_host_doing(collected["host"], s, e), (e - s) / 1e9]
                      for s, e in gaps[:10]],
    }


def _op_label(name: str, start: int, by_start, starts) -> str:
    """An XLA op's event name is its whole HLO instruction; keep the
    instruction's name, prefixed with the module that ran it."""
    op = name.split(" = ", 1)[0].lstrip("%")
    k = bisect.bisect_right(starts, start) - 1
    if k >= 0:
        mname, ms, md = by_start[k]
        if ms <= start < ms + md:
            return f"{mname.split('(', 1)[0]}/{op}"
    return op


def _host_doing(host, s, e) -> str:
    """The innermost host event that covers the middle of a gap."""
    mid = (s + e) / 2
    best = None
    for line, name, hs, hd in host:
        if name != WINDOW_SPAN and hs <= mid <= hs + hd:
            if best is None or hd < best[1]:
                best = (f"{name} [{line}]", hd)
    return best[0] if best else "no host event"
