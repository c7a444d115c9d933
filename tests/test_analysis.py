"""Invariant lint plane (ISSUE 11, ditl_tpu/analysis/).

- THE acceptance run: `python -m ditl_tpu.analysis` exits 0 over the real
  tree WITHOUT importing jax (the analyzer passes its own
  import-layering rule), and the analyzer package itself is clean under
  import-layering + thread-hygiene.
- Per-rule violating fixtures under tests/fixtures/analysis/ assert the
  exact rule id + line for every violation class, so the analyzer
  exits non-zero on each of them.
- Pragma grammar: a reasoned pragma suppresses; a reasonless or
  unknown-rule pragma is itself reported (rule id `pragma`).
- `--json` output shape + CLI exit codes (0 clean / 1 violations /
  2 usage).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import ditl_tpu
from ditl_tpu.analysis import RULES, Settings, hot_path, run
from ditl_tpu.analysis.__main__ import main

pytestmark = pytest.mark.analysis

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_DIR = os.path.dirname(os.path.abspath(ditl_tpu.__file__))
FIXTURES = os.path.join(REPO_ROOT, "tests", "fixtures", "analysis")


def fixture(name: str, pkg: str = "pkg") -> str:
    return os.path.join(FIXTURES, name, pkg)


def ids(diags):
    return [(d.rule, d.line) for d in diags]


# ---------------------------------------------------------------------------
# acceptance: the real tree is clean, and the analyzer is jax-free
# ---------------------------------------------------------------------------


def test_full_tree_clean_and_jax_free():
    """The CI entry point (ISSUE 11 satellite): the whole package passes
    every rule, and the pass itself never imports jax — asserted in a
    fresh interpreter so a conftest-loaded jax cannot mask a leak."""
    code = (
        "import sys\n"
        "from ditl_tpu.analysis.__main__ import main\n"
        "rc = main([])\n"
        "assert 'jax' not in sys.modules, 'jax leaked into the analyzer'\n"
        "sys.exit(rc)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=180,
        env={**os.environ},
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "0 violations" in out.stdout


def test_analyzer_package_passes_its_own_rules():
    """analysis/ is inside the import-layering zone and must also satisfy
    thread-hygiene (acceptance criterion)."""
    diags = run(PKG_DIR, rules=["import-layering", "thread-hygiene"])
    own = [d for d in diags if d.path.startswith("ditl_tpu/analysis/")]
    assert own == []


def test_every_pragma_in_tree_has_reason():
    """Acceptance: every pragma in the real tree carries a non-empty
    reason — run() reports reasonless ones under the `pragma` rule."""
    diags = run(PKG_DIR)
    assert [d for d in diags if d.rule == "pragma"] == []
    # and the tree actually USES the mechanism (memwatch lazy imports,
    # engine tick-ring casts, flight fast-path read) — the pragma grammar
    # is exercised by product code, not only by fixtures.
    from ditl_tpu.analysis.core import Project

    pragmas = [
        (f.display, p)
        for f in Project(PKG_DIR).files
        for p in f.pragmas
    ]
    assert len(pragmas) >= 5
    assert all(p.reason for _, p in pragmas)


# ---------------------------------------------------------------------------
# per-rule fixtures: exact rule id + line
# ---------------------------------------------------------------------------


def test_import_layering_fixture():
    diags = run(fixture("import_layering", "fakepkg"),
                rules=["import-layering"])
    assert ids(diags) == [
        ("import-layering", 2),   # bad_direct: module-level import jax
        ("import-layering", 2),   # bad_transitive: chain through heavy
        ("import-layering", 5),   # lazy: unsanctioned in-function import
    ]
    chain = [d for d in diags if "bad_transitive" in d.path]
    assert "fakepkg.heavy -> jax" in chain[0].message
    # the pragma'd lazy import and the TYPE_CHECKING import are silent
    assert not any("sanctioned" in d.message for d in diags)


def test_blocking_transfer_fixture():
    diags = run(fixture("hotpath"), rules=["blocking-transfer"])
    assert ids(diags) == [
        ("blocking-transfer", 11),  # jax.device_get
        ("blocking-transfer", 12),  # .block_until_ready()
        ("blocking-transfer", 13),  # float(name)
        ("blocking-transfer", 14),  # np.asarray(name)
        ("blocking-transfer", 15),  # int(attribute)
    ]
    assert all("Engine.tick" in d.message for d in diags)
    # float(len(...)) and the unmarked method are not flagged; the
    # pragma'd float(arr) on line 17 is suppressed.


def test_lock_discipline_fixture():
    diags = run(fixture("locks"), rules=["lock-discipline"])
    assert ids(diags) == [
        ("lock-discipline", 15),  # unlocked write
        ("lock-discipline", 18),  # unlocked read
    ]
    assert all("guarded-by _lock" in d.message for d in diags)
    # __init__ (defining method), the locked method, the *_locked
    # method, and the pragma'd racy read are all exempt.


def test_thread_hygiene_fixture():
    diags = run(fixture("threads"), rules=["thread-hygiene"])
    assert ids(diags) == [
        ("thread-hygiene", 7),    # bound thread, no join path
        ("thread-hygiene", 9),    # anonymous thread
        ("thread-hygiene", 23),   # executor without finally shutdown
    ]
    assert "anonymous" in diags[1].message
    # joined/daemonic threads and with/finally executors are silent.


def test_registry_mirror_fixture():
    settings = Settings(
        slo_canonical=("infer/continuous.py", "SLO_CLASSES"),
        slo_mirrors=(("gateway/admission.py", "SLO_CLASS_NAMES"),),
        chaos_registry=("chaos/plane.py", "SITES"),
    )
    diags = run(fixture("registry"), rules=["registry-mirror"],
                settings=settings)
    by_rule = ids(diags)
    assert ("registry-mirror", 7) in by_rule  # typo'd call site
    assert any("engine.tok" in d.message for d in diags)
    assert any("dead.site" in d.message
               and "consults it" in d.message for d in diags)
    drift = [d for d in diags if "drifted from canonical" in d.message]
    assert len(drift) == 1 and drift[0].line == 2
    assert len(diags) == 3


def test_config_drift_fixture():
    settings = Settings(config_module="config.py", docs=("docs.md",))
    diags = run(fixture("configdoc"), rules=["config-drift"],
                settings=settings)
    msgs = [d.message for d in diags]
    assert any("FooConfig.undocumented_field" in m for m in msgs)
    assert any("OrphanConfig is not a field of Config" in m for m in msgs)
    assert any("OrphanConfig.knob" in m for m in msgs)
    # documented_field (in docs.md) and metadata_field (inline doc) pass.
    assert not any("documented_field" in m and "undocumented" not in m
                   for m in msgs)
    assert not any("metadata_field" in m for m in msgs)


def test_metric_catalog_fixture():
    diags = run(fixture("metrics"), rules=["metric-catalog"])
    assert ids(diags) == [
        ("metric-catalog", 8),  # unknown counter (with _total appended)
        ("metric-catalog", 9),  # unknown gauge via resolved f-string
    ]
    assert "ditl_bogus_family_total" in diags[0].message
    assert "ditl_serving_made_up_gauge" in diags[1].message
    # the real family and the dynamically-built name are silent.


def test_tenant_label_discipline_fixture():
    diags = run(fixture("tenant"), rules=["tenant-label-discipline"])
    assert ids(diags) == [
        ("tenant-label-discipline", 14),  # raw bearer in a counter family
        ("tenant-label-discipline", 15),  # raw tenant in a journal event
    ]
    assert "bearer_token" in diags[0].message
    assert "tenant" in diags[1].message
    # the wrapped spellings (sanitize_label/tenant_label) stay silent.


def test_event_loop_hygiene_fixture():
    diags = run(fixture("evloop"), rules=["event-loop-hygiene"])
    assert ids(diags) == [
        ("event-loop-hygiene", 10),  # bad.py: sleep
        ("event-loop-hygiene", 11),  # bad.py: .sendall
        ("event-loop-hygiene", 12),  # bad.py: .join
        ("event-loop-hygiene", 13),  # bad.py: un-witnessed with self._lock
        ("event-loop-hygiene", 8),   # callbacks.py: sleep in registered fn
        ("event-loop-hygiene", 17),  # callbacks.py: .sendall in self-method
        ("event-loop-hygiene", 26),  # callbacks.py: sleep in lambda
    ]
    marked = [d for d in diags if d.path.endswith("bad.py")]
    assert all("Loop.tick" in d.message for d in marked)
    # Registered-callback resolution (ISSUE 18): no @event_loop marker in
    # callbacks.py — the rule resolved the registration targets.
    registered = [d for d in diags if d.path.endswith("callbacks.py")]
    assert all("loop callback" in d.message for d in registered)
    assert any("add_done_callback" in d.message for d in registered)
    assert any("<lambda>" in d.message for d in registered)
    # .send/.recv (non-blocking by construction on loop-owned sockets),
    # the guarded-by-witnessed lock, the pragma'd sleep, the unmarked
    # method, the blocking-but-never-registered function, and the
    # unresolvable registration target all stay silent.


def test_every_rule_has_a_violating_fixture():
    """Acceptance: the analyzer exits non-zero on every fixture violation
    class — each registered rule fires on its fixture."""
    registry_settings = Settings(
        slo_canonical=("infer/continuous.py", "SLO_CLASSES"),
        slo_mirrors=(("gateway/admission.py", "SLO_CLASS_NAMES"),),
        chaos_registry=("chaos/plane.py", "SITES"),
    )
    configdoc_settings = Settings(config_module="config.py",
                                  docs=("docs.md",))
    per_rule = {
        "import-layering": (fixture("import_layering", "fakepkg"), None),
        "blocking-transfer": (fixture("hotpath"), None),
        "lock-discipline": (fixture("locks"), None),
        "thread-hygiene": (fixture("threads"), None),
        "registry-mirror": (fixture("registry"), registry_settings),
        "config-drift": (fixture("configdoc"), configdoc_settings),
        "metric-catalog": (fixture("metrics"), None),
        "tenant-label-discipline": (fixture("tenant"), None),
        "event-loop-hygiene": (fixture("evloop"), None),
    }
    assert set(per_rule) == set(RULES), (
        "new rule registered without a violating fixture — add one under "
        "tests/fixtures/analysis/ and map it here"
    )
    for rule_id, (pkg, settings) in per_rule.items():
        diags = run(pkg, rules=[rule_id], settings=settings)
        assert any(d.rule == rule_id for d in diags), rule_id


# ---------------------------------------------------------------------------
# pragma grammar
# ---------------------------------------------------------------------------


def test_pragma_suppression_and_hygiene():
    diags = run(fixture("pragmas"), rules=["thread-hygiene"])
    # Line 7's violation is suppressed by the own-line pragma on line 6 —
    # but that pragma has no reason, which is itself reported.
    assert ("thread-hygiene", 7) not in ids(diags)
    assert ("pragma", 6) in ids(diags)
    # Line 9's pragma names an unknown rule: does NOT suppress, and the
    # bogus id is reported.
    assert ("thread-hygiene", 9) in ids(diags)
    assert any(d.rule == "pragma" and d.line == 9
               and "no-such-rule" in d.message for d in diags)
    # A reasoned pragma that suppresses NOTHING is stale — reported, so a
    # leftover suppression cannot silently eat the next violation on its
    # line. Only judged when the rules it names actually ran.
    assert any(d.rule == "pragma" and "suppresses nothing" in d.message
               for d in diags)
    other = run(fixture("pragmas"), rules=["lock-discipline"])
    assert not any("suppresses nothing" in d.message for d in other)


def test_repeated_rule_selection_runs_once():
    once = run(fixture("threads"), rules=["thread-hygiene"])
    twice = run(fixture("threads"),
                rules=["thread-hygiene", "thread-hygiene"])
    assert ids(once) == ids(twice)


def test_pragma_same_line_and_own_line_scoping():
    from ditl_tpu.analysis.core import Pragma

    trailing = Pragma(10, ("lock-discipline",), "why", own_line=False)
    assert trailing.covers("lock-discipline", 10)
    assert not trailing.covers("lock-discipline", 11)
    assert not trailing.covers("thread-hygiene", 10)
    own = Pragma(10, ("lock-discipline",), "why", own_line=True)
    assert own.covers("lock-discipline", 10)
    assert own.covers("lock-discipline", 11)
    assert not own.covers("lock-discipline", 12)


def test_pragma_in_docstring_is_not_a_pragma():
    """The grammar quoted in prose (docstrings, diagnostic messages) must
    not register — pragmas live in COMMENT tokens only. core.py itself
    quotes the grammar in its module docstring; if the scanner matched
    strings, the real tree's pragma audit above would be noise."""
    from ditl_tpu.analysis.core import Project

    core = [
        f for f in Project(PKG_DIR).files
        if f.rel == "analysis/core.py"
    ][0]
    assert '# ditl: allow(' in core.text  # the docstring quotes it
    assert core.pragmas == []  # but none registers


# ---------------------------------------------------------------------------
# CLI: exit codes + --json shape
# ---------------------------------------------------------------------------


def test_cli_json_shape(capsys):
    rc = main(["--root", fixture("threads"), "--rule", "thread-hygiene",
               "--json"])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["schema"] == 1
    assert payload["clean"] is False
    assert payload["rules"] == ["thread-hygiene"]
    assert payload["violations"] == len(payload["diagnostics"]) == 3
    d = payload["diagnostics"][0]
    assert set(d) == {"rule", "path", "line", "message"}
    assert d["rule"] == "thread-hygiene"
    assert isinstance(d["line"], int)


def test_cli_exit_codes(capsys):
    assert main(["--root", PKG_DIR]) == 0
    # unknown rule id = usage error (exit 2), never a silent pass
    assert main(["--root", PKG_DIR, "--rule", "no-such-rule"]) == 2
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in RULES:
        assert rule_id in out


def test_cli_single_rule_violation_exits_nonzero(capsys):
    rc = main(["--root", fixture("locks"), "--rule", "lock-discipline"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "[lock-discipline]" in out and "2 violation(s)" in out


# ---------------------------------------------------------------------------
# row stamp + perf_compare gating (CI/tooling satellite)
# ---------------------------------------------------------------------------


def test_bench_rows_stamp_analysis_clean():
    """Every row the fleet drivers hand to perf_compare carries the
    invariant-lint verdict (computed once per process); on this tree it
    must be True."""
    from tests import gateway_drivers

    meta = gateway_drivers._record_meta()
    assert meta["analysis_clean"] is True
    assert "schema" in meta and "git_rev" in meta
    # cached: the second call must not re-run the analyzer
    assert gateway_drivers._record_meta()["analysis_clean"] is True


def test_perf_compare_gates_newly_dirty_tree():
    """analysis_clean true -> false is a "now fails"-class regression
    (like incidents); both-dirty and stamp-less rows are not gated."""
    from ditl_tpu.telemetry.perf_compare import compare_records

    clean = {"metric": "tok/s", "value": 100.0, "analysis_clean": True}
    dirty = {"metric": "tok/s", "value": 120.0, "analysis_clean": False}
    code, report = compare_records(clean, dirty, 0.05)
    assert code == 1 and "analysis_clean: true -> false" in report
    # both dirty: reported, not gated
    code, report = compare_records(
        {**clean, "analysis_clean": False}, dirty, 0.05)
    assert code == 0 and "not gated" in report
    # old rows predate the stamp: not gated
    code, _ = compare_records({"metric": "tok/s", "value": 100.0},
                              dirty, 0.05)
    assert code == 0
    # cleaned up: never a regression
    code, _ = compare_records(dirty, {**clean, "value": 120.0}, 0.05)
    assert code == 0


# ---------------------------------------------------------------------------
# annotations
# ---------------------------------------------------------------------------


def test_hot_path_decorator_is_noop_marker():
    @hot_path
    def f(x):
        return x + 1

    assert f(1) == 2
    assert getattr(f, "__ditl_hot_path__") is True


def test_hot_path_applied_at_the_contract_sites():
    """The seams ISSUE 11 names carry the marker (so the rule actually
    binds them): the engine tick loop, the flight-ring record path, and
    the MetricsLogger record methods."""
    from ditl_tpu.telemetry.flight import FlightRing

    assert getattr(FlightRing.record, "__ditl_hot_path__", False)
    import importlib

    metrics_mod = importlib.import_module("ditl_tpu.train.metrics")
    logger_cls = metrics_mod.MetricsLogger
    assert getattr(logger_cls.start_step, "__ditl_hot_path__", False)
    assert getattr(logger_cls.end_step, "__ditl_hot_path__", False)
    from ditl_tpu.infer.continuous import ContinuousEngine

    assert getattr(ContinuousEngine.step, "__ditl_hot_path__", False)
