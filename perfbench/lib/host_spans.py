"""The program's own host spans in a JAX profiler trace, summed per name
over the benchmark's window.

The program opens `verify.*` spans inside each provider call
(corda_tpu/obs/trace.span); in a profiler session each is a host event on
the device trace's clock, with its stats (`lanes`, `bucket`). `collect`
keeps those events, and the driver's own `perfbench.*` spans, with their
stats; `reduce` gives, per name, the seconds inside the window, the count
of events that overlap it and the sum of each stat over them. The driver
and the readers do not call this yet (PERF.md section 7).
"""

from __future__ import annotations

import glob
import os

from perfbench.lib.trace import WINDOW_SPAN

PREFIXES = ("verify.", "perfbench.")


def collect(trace_dir: str, prefixes: tuple = PREFIXES) -> dict:
    """[name, start_ns, duration_ns, stats] of every host event of the
    newest trace under `trace_dir` whose name starts with one of
    `prefixes`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    spans = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    spans.append([ev.name, ev.start_ns, ev.duration_ns,
                                  {k: v for k, v in dict(ev.stats).items()
                                   if isinstance(v, (int, float))}])
    return {"spans": spans}


def reduce(collected: dict) -> dict | None:
    """{name: {"s": seconds inside the window, "n": events that overlap
    it, "stats": {stat: sum over those events}}} for every collected name
    but the window's own; None without a window span."""
    windows = [(s, s + d) for name, s, d, _ in collected["spans"]
               if name == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    out: dict = {}
    for name, s, d, stats in collected["spans"]:
        inside = min(s + d, hi) - max(s, lo)
        if name == WINDOW_SPAN or inside <= 0:
            continue
        entry = out.setdefault(name, {"s": 0.0, "n": 0, "stats": {}})
        entry["s"] += inside / 1e9
        entry["n"] += 1
        for k, v in stats.items():
            entry["stats"][k] = entry["stats"].get(k, 0) + v
    return out
