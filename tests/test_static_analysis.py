"""Invariant analyzer: tier-1 gate + rule-engine coverage.

The first test is the merge-blocker: zero live findings over the shipped
tree. The rest prove each rule actually fires (a lint pass that never
fires enforces nothing), that suppressions demand reasons, and that the
baseline can only shrink.
"""

import json
import time
from pathlib import Path

from corda_tpu.analysis import (
    ALL_RULES,
    analyze_paths,
    analyze_source,
    baseline_entries_from_findings,
    load_baseline,
)
from corda_tpu.analysis.__main__ import main as cli_main

REPO = Path(__file__).resolve().parents[1]
TREE = REPO / "corda_tpu"

RAFT_PATH = "corda_tpu/node/services/raft.py"  # in-scope for wallclock rule


def _rules(report):
    return [f.rule for f in report.findings]


# ---------------------------------------------------------------------------
# The tier-1 gate
# ---------------------------------------------------------------------------


class TestTreeGate:
    def test_tree_has_zero_unbaselined_findings(self):
        t0 = time.perf_counter()
        report = analyze_paths([TREE])
        elapsed = time.perf_counter() - t0
        assert len(report.rules) >= 6
        assert report.checked_files > 100
        rendered = "\n".join(f.render() for f in report.findings)
        assert report.clean, f"live invariant findings:\n{rendered}"
        # ISSUE budget: the gate must stay cheap enough for tier-1.
        assert elapsed < 5.0, f"analyzer took {elapsed:.1f}s on the tree"

    def test_every_suppression_in_tree_was_exercised(self):
        # The tree carries reasoned allow() comments; each must suppress a
        # real finding (dead suppressions rot like dead baselines).
        report = analyze_paths([TREE])
        assert len(report.suppressed) >= 15

    def test_ingest_hot_path_is_in_scope_and_clean(self):
        # Round 15: the vectorized ingest plane is the highest-frequency
        # client-side loop in the tree — pin it in-scope explicitly so a
        # future exclude-list edit can't silently drop it from the gate
        # (no per-item jit/wallclock/silent-except regressions).
        report = analyze_paths([TREE / "tools" / "ingest.py",
                                TREE / "crypto" / "batch_sign.py",
                                TREE / "tools" / "loadgen.py"])
        assert report.checked_files == 3
        rendered = "\n".join(f.render() for f in report.findings)
        assert report.clean, f"ingest-plane findings:\n{rendered}"

    def test_checked_in_baseline_entries_are_live_files_with_reasons(self):
        # The baseline shrinks monotonically (round 12 resolved the last
        # two entries at source, so empty is the healthy end state); any
        # entry that IS carried must name a live file and a reason.
        path = REPO / "corda_tpu/analysis/baseline.json"
        assert path.exists(), "baseline file missing"
        for e in load_baseline(path):
            assert (REPO / e["path"]).exists(), e["path"]
            assert str(e.get("reason", "")).strip(), e


# ---------------------------------------------------------------------------
# Rule fixtures: violating + clean + suppressed (+ baselined)
# ---------------------------------------------------------------------------


class TestNoWallclockInApply:
    def test_replica_side_epoch_read_goes_red(self):
        src = (
            "import time as _time\n"
            "def _apply_reserve(db, cmd):\n"
            "    return _time.time() > cmd.issued_at + cmd.ttl_s\n"
        )
        report = analyze_source(src, RAFT_PATH)
        assert "no-wallclock-in-apply" in _rules(report)

    def test_monotonic_inside_apply_goes_red(self):
        src = (
            "import time\n"
            "def make_apply_command(db):\n"
            "    def helper():\n"
            "        return time.monotonic()\n"
            "    return helper\n"
        )
        report = analyze_source(src, RAFT_PATH)
        assert "no-wallclock-in-apply" in _rules(report)

    def test_monotonic_deadline_outside_apply_is_clean(self):
        src = (
            "import time as _time\n"
            "def poll(deadline):\n"
            "    return _time.monotonic() >= deadline\n"
        )
        report = analyze_source(src, RAFT_PATH)
        assert "no-wallclock-in-apply" not in _rules(report)

    def test_out_of_scope_file_is_ignored(self):
        src = "import time\nx = time.time()\n"
        report = analyze_source(src, "corda_tpu/tools/loadtest.py")
        assert "no-wallclock-in-apply" not in _rules(report)

    def test_real_coordinator_stamping_sites_stay_green(self):
        # The three ISSUE-named stamping sites fire the rule and are
        # absorbed by their reasoned allow() comments — never live.
        report = analyze_paths(
            [TREE / "node/services/sharding.py",
             TREE / "node/services/raft.py"],
            use_baseline=False)
        assert "no-wallclock-in-apply" not in _rules(report)
        stamped = [f for f in report.suppressed
                   if f.rule == "no-wallclock-in-apply"]
        assert len(stamped) >= 3


class TestNoSilentExcept:
    VIOLATION = (
        "def f(handler):\n"
        "    try:\n"
        "        handler()\n"
        "    except Exception:\n"
        "        pass\n"
    )

    def test_silent_pass_goes_red(self):
        report = analyze_source(self.VIOLATION, "corda_tpu/node/x.py")
        assert "no-silent-except" in _rules(report)

    def test_bare_except_goes_red(self):
        src = "def f(g):\n    try:\n        g()\n    except:\n        pass\n"
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "no-silent-except" in _rules(report)

    def test_narrowed_or_counting_handler_is_clean(self):
        src = (
            "def f(handler, metrics):\n"
            "    try:\n"
            "        handler()\n"
            "    except (LookupError, ValueError):\n"
            "        pass\n"
            "    try:\n"
            "        handler()\n"
            "    except Exception:\n"
            "        metrics['fails'] += 1\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "no-silent-except" not in _rules(report)

    def test_reasoned_allow_suppresses(self):
        src = (
            "def f(handler):\n"
            "    try:\n"
            "        handler()\n"
            "    # lint: allow(no-silent-except) demo tooling, retried next tick\n"
            "    except Exception:\n"
            "        pass\n"
        )
        report = analyze_source(src, "corda_tpu/tools/x.py")
        assert "no-silent-except" not in _rules(report)
        assert len(report.suppressed) == 1

    def test_baseline_absorbs_enumerated_site(self):
        entries = [{"rule": "no-silent-except", "path": "corda_tpu/node/x.py",
                    "code": "except Exception:", "count": 1,
                    "reason": "pre-existing, tracked"}]
        report = analyze_source(self.VIOLATION, "corda_tpu/node/x.py",
                                baseline_entries=entries)
        assert "no-silent-except" not in _rules(report)
        assert len(report.baselined) == 1


class TestNoJitInHotpath:
    def test_jit_inside_per_batch_function_goes_red(self):
        src = (
            "import jax\n"
            "def verify_batch(fn, xs):\n"
            "    return jax.jit(fn)(xs)\n"
        )
        report = analyze_source(src, "corda_tpu/ops/x.py")
        assert "no-jit-in-hotpath" in _rules(report)

    def test_mesh_construction_inside_function_goes_red(self):
        src = (
            "from jax.sharding import Mesh\n"
            "def dispatch(devs, xs):\n"
            "    return Mesh(devs, ('sigs',))\n"
        )
        report = analyze_source(src, "corda_tpu/ops/x.py")
        assert "no-jit-in-hotpath" in _rules(report)

    def test_module_level_and_cached_builders_are_clean(self):
        src = (
            "import functools\n"
            "import jax\n"
            "def _graph(x):\n"
            "    return x\n"
            "verify = jax.jit(_graph)\n"
            "@functools.lru_cache(maxsize=None)\n"
            "def builder(mesh):\n"
            "    return jax.jit(_graph)\n"
        )
        report = analyze_source(src, "corda_tpu/ops/x.py")
        assert "no-jit-in-hotpath" not in _rules(report)

    def test_module_level_jit_decorator_is_clean(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def verify_arrays(x):\n"
            "    return x\n"
        )
        report = analyze_source(src, "corda_tpu/ops/x.py")
        assert "no-jit-in-hotpath" not in _rules(report)


class TestNoBlockingUnderLock:
    def test_socket_send_under_lock_goes_red(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self, sock):\n"
            "        self._lock = threading.Lock()\n"
            "        self.sock = sock\n"
            "    def send(self, buf):\n"
            "        with self._lock:\n"
            "            self.sock.sendall(buf)\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "no-blocking-under-lock" in _rules(report)

    def test_sqlite_under_designated_db_lock_is_exempt(self):
        src = (
            "class C:\n"
            "    def put(self, row):\n"
            "        with self.db.lock:\n"
            "            self.db.conn.execute('INSERT', row)\n"
            "            self.db.conn.commit()\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "no-blocking-under-lock" not in _rules(report)

    def test_copy_under_lock_send_outside_is_clean(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self, sock):\n"
            "        self._lock = threading.Lock()\n"
            "        self.sock = sock\n"
            "        self.queue = []\n"
            "    def flush(self):\n"
            "        with self._lock:\n"
            "            batch = list(self.queue)\n"
            "        self.sock.sendall(b''.join(batch))\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "no-blocking-under-lock" not in _rules(report)

    def test_condition_wait_is_exempt(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._cv = threading.Condition()\n"
            "    def park(self):\n"
            "        with self._cv:\n"
            "            self._cv.wait(0.1)\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "no-blocking-under-lock" not in _rules(report)

    def test_allow_on_with_line_suppresses(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self, sock):\n"
            "        self._lock = threading.Lock()\n"
            "        self.sock = sock\n"
            "    def send(self, buf):\n"
            "        # lint: allow(no-blocking-under-lock) this lock serializes the socket\n"
            "        with self._lock:\n"
            "            self.sock.sendall(buf)\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "no-blocking-under-lock" not in _rules(report)
        assert len(report.suppressed) == 1


class TestLockOrder:
    def test_acquisition_cycle_goes_red(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b_lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._a:\n"
            "            with self._b_lock:\n"
            "                pass\n"
            "    def g(self):\n"
            "        with self._b_lock:\n"
            "            with self._a:\n"
            "                pass\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "lock-order" in _rules(report)

    def test_self_reacquire_goes_red(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            with self._lock:\n"
            "                pass\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "lock-order" in _rules(report)

    def test_consistent_global_order_is_clean(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "    def f(self, other):\n"
            "        with self._a:\n"
            "            with other.stats_lock:\n"
            "                pass\n"
            "    def g(self, other):\n"
            "        with self._a:\n"
            "            with other.stats_lock:\n"
            "                pass\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "lock-order" not in _rules(report)

    def test_same_attr_in_different_classes_is_not_a_cycle(self):
        # `self._lock` in two unrelated classes must not alias.
        src = (
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.peer_lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            with self.peer_lock:\n"
            "                pass\n"
            "class B:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.peer_lock = threading.Lock()\n"
            "    def g(self):\n"
            "        with self.peer_lock:\n"
            "            with self._lock:\n"
            "                pass\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "lock-order" not in _rules(report)


class TestTraceStageRegistry:
    def test_unregistered_literal_goes_red(self):
        src = (
            "from ..obs import trace as _obs\n"
            "def f(t0, t1):\n"
            "    _obs.record('device_vrfy', t0, t1)\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "trace-stage-registry" in _rules(report)

    def test_registered_names_and_flow_prefix_are_clean(self):
        src = (
            "from ..obs import trace as _obs\n"
            "def f(t0, t1, name):\n"
            "    _obs.record('device_verify', t0, t1)\n"
            "    _obs.record('raft_commit', t0, t1)\n"
            "    _obs.record(f'flow:{name}', t0, t1)\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "trace-stage-registry" not in _rules(report)

    def test_unregistered_dynamic_prefix_goes_red(self):
        src = (
            "from ..obs import trace as _obs\n"
            "def f(t0, t1, name):\n"
            "    _obs.record(f'stage:{name}', t0, t1)\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "trace-stage-registry" in _rules(report)

    def test_variable_names_and_obs_internal_sites_are_skipped(self):
        src = (
            "from ..obs import trace as _obs\n"
            "def f(t0, t1, name):\n"
            "    _obs.record(name, t0, t1)\n"
        )
        assert "trace-stage-registry" not in _rules(
            analyze_source(src, "corda_tpu/node/x.py"))
        red = "from . import trace as _obs\ndef f():\n    _obs.record('x', 0, 1)\n"
        assert "trace-stage-registry" not in _rules(
            analyze_source(red, "corda_tpu/obs/collect.py"))

    def test_unregistered_span_helper_literal_goes_red(self):
        src = (
            "from ..obs import trace as _obs\n"
            "from ..obs.trace import span\n"
            "def f():\n"
            "    with _obs.span('verify.pakc'):\n"
            "        pass\n"
            "    with span('verify.dispatch', lanes=1, bucket=64):\n"
            "        pass\n"
            "    with span('verify.readbak'):\n"
            "        pass\n"
        )
        report = analyze_source(src, "corda_tpu/ops/x.py")
        assert _rules(report).count("trace-stage-registry") == 2

    def test_registered_verify_spans_are_clean(self):
        from corda_tpu.obs import stages

        body = "".join(f"    with _obs.span({n!r}):\n        pass\n"
                       for n in stages.VERIFY_SPANS)
        src = "from ..obs import trace as _obs\ndef f():\n" + body
        report = analyze_source(src, "corda_tpu/crypto/x.py")
        assert "trace-stage-registry" not in _rules(report)
        assert set(stages.VERIFY_SPANS) <= stages.SPAN_NAMES

    def test_registry_and_breakdown_share_one_source_of_truth(self):
        from corda_tpu.obs import collect, stages

        assert collect.STAGES is stages.STAGES
        assert set(stages.BATCH_STAGES) <= set(stages.STAGES)
        assert set(stages.DIRECT_STAGES) <= set(stages.STAGES)
        assert set(stages.DERIVED_STAGES) <= set(stages.STAGES)

    # Round 16: the rule also covers telemetry metric names — a typo'd
    # inc()/observe() literal raises ValueError at runtime (possibly only
    # on a rare error path), so it must go red at lint time.

    def test_unregistered_telemetry_metric_goes_red(self):
        src = (
            "from ..obs import telemetry as _tm\n"
            "def f():\n"
            "    _tm.inc('verify_batchs_total')\n"
            "    _tm.observe('round_wall_seconds', 0.1)\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert _rules(report).count("trace-stage-registry") == 1

    def test_registered_telemetry_metric_names_are_clean(self):
        src = (
            "from ..obs import telemetry as _tm\n"
            "from ..obs.telemetry import inc\n"
            "def f(n):\n"
            "    _tm.inc('verify_batches_total')\n"
            "    _tm.observe('verify_batch_sigs', n)\n"
            "    inc('rounds_total')\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "trace-stage-registry" not in _rules(report)

    def test_from_imported_inc_with_unknown_name_goes_red(self):
        src = (
            "from ..obs.telemetry import inc as _inc\n"
            "def f():\n"
            "    _inc('made_up_total')\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "trace-stage-registry" in _rules(report)

    def test_variable_metric_names_are_skipped(self):
        # Dynamic names are the runtime registry's job (inc raises on an
        # unregistered name) — the lexical rule only judges literals.
        src = (
            "from ..obs import telemetry as _tm\n"
            "def f(name):\n"
            "    _tm.inc(name)\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "trace-stage-registry" not in _rules(report)


# ---------------------------------------------------------------------------
# Suppression + baseline machinery
# ---------------------------------------------------------------------------


class TestSuppressions:
    def test_allow_without_reason_is_itself_a_finding(self):
        src = (
            "def f(g):\n"
            "    try:\n"
            "        g()\n"
            "    # lint: allow(no-silent-except)\n"
            "    except Exception:\n"
            "        pass\n"
        )
        report = analyze_source(src, "corda_tpu/node/x.py")
        rules = _rules(report)
        assert "bad-suppression" in rules
        assert "no-silent-except" in rules  # the reasonless allow is void

    def test_allow_naming_unknown_rule_is_a_finding(self):
        src = "# lint: allow(no-such-rule) because reasons\nx = 1\n"
        report = analyze_source(src, "corda_tpu/node/x.py")
        assert "bad-suppression" in _rules(report)

    def test_trailing_allow_on_same_line_works(self):
        src = (
            "import time as _time\n"
            "def f():\n"
            "    return _time.time()  # lint: allow(no-wallclock-in-apply) coordinator stamp\n"
        )
        report = analyze_source(src, RAFT_PATH)
        assert "no-wallclock-in-apply" not in _rules(report)
        assert len(report.suppressed) == 1


class TestBaseline:
    def test_round_trip(self):
        src = TestNoSilentExcept.VIOLATION
        first = analyze_source(src, "corda_tpu/node/x.py")
        entries = baseline_entries_from_findings(first.findings,
                                                 "accepted pre-existing")
        second = analyze_source(src, "corda_tpu/node/x.py",
                                baseline_entries=entries)
        assert second.clean
        assert len(second.baselined) == len(first.findings)

    def test_entry_for_missing_file_goes_stale(self):
        entries = [{"rule": "no-silent-except",
                    "path": "corda_tpu/node/deleted.py",
                    "code": "except Exception:", "count": 1,
                    "reason": "was accepted"}]
        report = analyze_source("x = 1\n", "corda_tpu/node/x.py",
                                baseline_entries=entries)
        assert "stale-baseline" in _rules(report)

    def test_unmatched_and_reasonless_entries_go_stale(self):
        entries = [
            {"rule": "no-silent-except", "path": "corda_tpu/node/x.py",
             "code": "except Exception:", "count": 1, "reason": "fixed?"},
            {"rule": "no-silent-except", "path": "corda_tpu/node/x.py",
             "code": "except BaseException:", "count": 1, "reason": ""},
        ]
        report = analyze_source("x = 1\n", "corda_tpu/node/x.py",
                                baseline_entries=entries)
        assert _rules(report).count("stale-baseline") == 2

    def test_budget_absorbs_count_then_surfaces_excess(self):
        src = TestNoSilentExcept.VIOLATION * 2  # two identical sites
        entries = [{"rule": "no-silent-except", "path": "corda_tpu/node/x.py",
                    "code": "except Exception:", "count": 1,
                    "reason": "only one accepted"}]
        report = analyze_source(src, "corda_tpu/node/x.py",
                                baseline_entries=entries)
        assert _rules(report).count("no-silent-except") == 1
        assert len(report.baselined) == 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


class TestCli:
    def test_json_mode_and_exit_codes(self, tmp_path, capsys):
        bad = tmp_path / "corda_tpu" / "node" / "x.py"
        bad.parent.mkdir(parents=True)
        bad.write_text(TestNoSilentExcept.VIOLATION)
        rc = cli_main(["--json", "--no-baseline", str(bad)])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert doc["clean"] is False
        assert doc["findings"][0]["rule"] == "no-silent-except"
        assert doc["findings"][0]["line"] == 4

        good = tmp_path / "corda_tpu" / "node" / "y.py"
        good.write_text("x = 1\n")
        rc = cli_main(["--json", "--no-baseline", str(good)])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert doc["clean"] is True

    def test_list_rules_names_all_six(self, capsys):
        assert cli_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ALL_RULES:
            assert rule.name in out
        assert len(ALL_RULES) >= 6

    def test_bench_report_stamp_is_zero(self):
        # What bench.py embeds in the report header: live findings on the
        # shipped tree via the checked-in baseline.
        report = analyze_paths([TREE])
        assert len(report.findings) == 0
