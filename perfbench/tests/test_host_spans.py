"""lib/host_spans.py: the program's verify.* spans summed over the window,
on a synthetic trace and on a short trace recorded on a TPU v5e.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import host_spans, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHILDREN = ("verify.prepare", "verify.pack", "verify.dispatch",
            "verify.readback", "verify.scatter")


def _lane_fill(spans: dict) -> float:
    stats = spans["verify.dispatch"]["stats"]
    return 100.0 * stats["lanes"] / stats["bucket"]


def test_reduce_clips_to_the_window_and_sums_per_name():
    spans = [
        [trace.WINDOW_SPAN, 1000, 9000, {}],  # window [1000, 10000)
        ["verify.batch", 500, 3500, {"lanes": 100}],  # 3000 ns inside
        ["verify.dispatch", 2000, 100, {"lanes": 90, "bucket": 128}],
        ["verify.batch", 5000, 4000, {"lanes": 100}],
        ["verify.dispatch", 6000, 200, {"lanes": 92, "bucket": 128}],
        ["verify.dispatch", 10000, 50, {"lanes": 1, "bucket": 64}],  # after
        ["verify.batch", 9500, 2000, {"lanes": 100}],  # 500 ns inside
    ]
    red = host_spans.reduce({"spans": spans})
    assert trace.WINDOW_SPAN not in red
    assert red["verify.batch"]["s"] == pytest.approx(7.5e-6)
    assert red["verify.batch"]["n"] == 3
    assert red["verify.batch"]["stats"] == {"lanes": 300}
    assert red["verify.dispatch"]["s"] == pytest.approx(3e-7)
    assert red["verify.dispatch"]["stats"] == {"lanes": 182, "bucket": 256}
    assert _lane_fill(red) == pytest.approx(100.0 * 182 / 256)


def test_reduce_without_a_window_reads_nothing():
    assert host_spans.reduce(
        {"spans": [["verify.batch", 0, 10, {"lanes": 1}]]}) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "bulk_spans_trace_tpu.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_tpu_trace_has_the_spans_of_every_call(recorded):
    red = host_spans.reduce(recorded)
    calls = red["perfbench.verify_batch"]["n"]
    assert calls >= 3
    assert red["verify.batch"]["n"] == calls
    assert red["verify.batch"]["stats"]["lanes"] == 100_000 * calls
    assert red["verify.dispatch"]["n"] == calls
    # 12 malformed lanes per batch, 8 of them filtered before the device.
    assert red["verify.dispatch"]["stats"] == {"lanes": 99_992 * calls,
                                               "bucket": 131_072 * calls}
    assert _lane_fill(red) == pytest.approx(76.2878, abs=1e-4)
    # The provider call is the driver's call, and its children cover it.
    driver = red["perfbench.verify_batch"]["s"]
    batch = red["verify.batch"]["s"]
    assert 0.95 * driver <= batch <= driver
    children = sum(red[n]["s"] for n in CHILDREN)
    assert 0.95 * batch <= children <= batch


def test_recorded_tpu_trace_names_its_idle_gaps_by_verify_spans(recorded):
    red = trace.reduce(recorded, {"verify": "verify_arrays_pallas",
                                  "challenge": "challenge_words"})
    # Seven calls leave seven gaps between kernels; the rest are
    # microseconds inside one call.
    long = [name for name, s in red["idle_gaps"] if s > 1e-3]
    assert len(long) >= 7
    assert all(name.startswith("verify.") for name in long), long
