"""The verify path's spans (obs.trace.span): in a jax.profiler trace as host
events that nest inside the provider call and carry their stats, in the
obs ring with the same names, and nothing at all with both switches off.

One 64-lane bucket of the XLA graph (the CPU's device tier), compiled once
for the module before any trace starts.
"""

import glob
import os

import numpy as np
import pytest

import jax

from corda_tpu.crypto import ref_ed25519 as ref
from corda_tpu.crypto.provider import VerifyJob, make_verifier
from corda_tpu.obs import stages
from corda_tpu.obs import trace as obs
from corda_tpu.ops import ed25519_jax

N_JOBS = 12
MALFORMED = {3: "short key", 7: "long signature"}


def _jobs() -> tuple[list, np.ndarray]:
    """N_JOBS jobs: real signatures, one tampered, two malformed lanes."""
    jobs, truth = [], []
    for i in range(N_JOBS):
        seed, msg = bytes([i + 1]) * 32, bytes([0x40 + i]) * 32
        pk, sig = ref.public_key(seed), ref.sign(seed, msg)
        if i == 5:
            msg = bytes([msg[0] ^ 1]) + msg[1:]
        if MALFORMED.get(i) == "short key":
            pk = pk[:31]
        elif MALFORMED.get(i) == "long signature":
            sig = sig + b"\0"
        jobs.append(VerifyJob(pk, msg, sig))
        truth.append(i != 5 and i not in MALFORMED)
    return jobs, np.array(truth)


@pytest.fixture(scope="module")
def verifier():
    v = make_verifier("jax")
    v.device_min_sigs = 0  # every batch takes the device tier
    jobs, truth = _jobs()
    assert np.array_equal(v.verify_batch(jobs), truth)  # compiles, untraced
    return v


def _expected_spans() -> set:
    """The native pass reads the jobs straight into packed words under
    verify.pack; without the native core they become columns under
    verify.prepare first."""
    if getattr(ed25519_jax._cpack_module(), "pack_jobs", None) is None:
        return set(stages.VERIFY_SPANS)
    return set(stages.VERIFY_SPANS) - {"verify.prepare"}


def _host_events(trace_dir) -> list:
    """(name, start_ns, end_ns, stats) of every verify.* host event."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("verify."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_profiler_trace_holds_nested_verify_spans(verifier, tmp_path):
    jobs, truth = _jobs()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        got = verifier.verify_batch(jobs)
    finally:
        jax.profiler.stop_trace()
    assert np.array_equal(got, truth)
    events = _host_events(tmp_path)
    assert {e[0] for e in events} == _expected_spans()
    (_, lo, hi, stats), = [e for e in events if e[0] == "verify.batch"]
    assert stats == {"lanes": N_JOBS, "split": 0}
    children = [e for e in events if e[0] != "verify.batch"]
    assert all(lo <= s <= e <= hi for _, s, e, _ in children)
    (_, _, _, dispatch), = [e for e in events if e[0] == "verify.dispatch"]
    well_formed = N_JOBS - len(MALFORMED)
    assert dispatch == {"lanes": well_formed,
                        "bucket": ed25519_jax.pick_bucket(well_formed)}
    # Disjoint children: their sum cannot exceed the call.
    assert sum(e - s for _, s, e, _ in children) <= hi - lo


def test_armed_ring_holds_the_same_spans(verifier):
    jobs, truth = _jobs()
    rec = obs.arm("verify")
    try:
        assert np.array_equal(verifier.verify_batch(jobs), truth)
        assert obs.get_context() is None  # restored after the call
    finally:
        obs.disarm()
    spans = rec.snapshot()
    assert {s["name"] for s in spans} == _expected_spans()
    root, = [s for s in spans if s["name"] == "verify.batch"]
    assert root["parent"] is None
    assert root["attrs"] == {"lanes": N_JOBS, "split": 0}
    for s in spans:
        if s is not root:
            assert s["trace_id"] == root["trace_id"]
            assert s["parent"] == root["span_id"]
            assert root["t_start"] <= s["t_start"] <= s["t_end"] \
                <= root["t_end"]
    dispatch, = [s for s in spans if s["name"] == "verify.dispatch"]
    assert dispatch["attrs"]["lanes"] == N_JOBS - len(MALFORMED)


def test_ring_span_parents_to_the_current_context():
    rec = obs.arm("ctx")
    try:
        obs.set_context(b"t" * 8, b"p" * 8)
        with obs.span("verify.pack"):
            inner = obs.get_context()
        assert obs.get_context() == (b"t" * 8, b"p" * 8)
    finally:
        obs.disarm()
    s, = rec.snapshot()
    assert s["trace_id"] == (b"t" * 8).hex()
    assert s["parent"] == (b"p" * 8).hex()
    assert inner == (b"t" * 8, bytes.fromhex(s["span_id"]))
    assert s["attrs"] == {}


class _RefusedAnnotation:
    """The profiler's annotation type with no session running: any
    annotation built while it reports disabled is a bug."""

    @staticmethod
    def is_enabled() -> bool:
        return False

    def __init__(self, *a, **kw):
        raise AssertionError("annotation built with the profiler off")


def test_disarmed_verify_path_allocates_nothing(verifier, monkeypatch):
    assert obs.ACTIVE is None
    assert not jax.profiler.TraceAnnotation.is_enabled()

    def _boom(*a, **kw):
        raise AssertionError("tracing touched while disarmed")

    monkeypatch.setattr(obs, "new_trace_id", _boom)
    monkeypatch.setattr(obs, "new_span_id", _boom)
    monkeypatch.setattr(obs, "_LiveSpan", _boom)
    monkeypatch.setattr(obs.SpanRecorder, "record", _boom)
    monkeypatch.setattr(obs, "_ANNOTATION", _RefusedAnnotation)
    jobs, truth = _jobs()
    assert np.array_equal(verifier.verify_batch(jobs), truth)
    assert obs.span("verify.batch", lanes=1) is obs._NO_SPAN


def test_profiler_on_with_ring_disarmed_builds_no_ring_span(monkeypatch):
    built = []

    class _Annotation:
        @staticmethod
        def is_enabled() -> bool:
            return True

        def __init__(self, name, **stats):
            built.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def _boom(*a, **kw):
        raise AssertionError("ring touched while disarmed")

    monkeypatch.setattr(obs, "_ANNOTATION", _Annotation)
    monkeypatch.setattr(obs, "new_span_id", _boom)
    with obs.span("verify.dispatch", lanes=3, bucket=64):
        pass
    assert built == [("verify.dispatch", {"lanes": 3, "bucket": 64})]
