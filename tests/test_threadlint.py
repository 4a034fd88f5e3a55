"""Static concurrency lint (core/concurrency_analysis.py + threadlint):
one seeded fixture module per CC1xx rule asserting rule id + file + line,
a clean-run assertion over the whole package (every waiver accounted
for), waiver syntax/count semantics, CLI exit codes, telemetry counters,
and pallas_kernels/adoption.py, where the lint once found blocking under a
lock, linting clean."""

import contextlib
import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

import paddle_tpu as fluid
from paddle_tpu.core import telemetry
from paddle_tpu.core.concurrency_analysis import (
    CC_RULES,
    analyze_paths,
    expected_findings,
    report_telemetry,
)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FIXTURES = os.path.join(_ROOT, "tests", "threadlint_fixtures")
_PKG = os.path.join(_ROOT, "paddle_tpu")


def _fixture(rule):
    return os.path.join(_FIXTURES, "%s_seed.py" % rule.lower())


@contextlib.contextmanager
def _flags(**kv):
    kv = {("FLAGS_" + k if not k.startswith("FLAGS_") else k): v
          for k, v in kv.items()}
    old = fluid.get_flags(list(kv))
    fluid.set_flags(kv)
    try:
        yield
    finally:
        fluid.set_flags(old)


# -- seeded fixtures: every rule fires at the exact marked line -------------


@pytest.mark.parametrize("rule", sorted(CC_RULES))
def test_seeded_fixture_fires(rule):
    path = _fixture(rule)
    assert os.path.exists(path), "missing seeded fixture for %s" % rule
    expected = [(r, ln) for r, ln in expected_findings(path) if r == rule]
    assert expected, "fixture carries no threadlint-expect marker"
    report = analyze_paths([path])
    got = {(d.rule, d.line) for d in report.diagnostics if not d.waived}
    for want in expected:
        assert want in got, (
            "%s not reported at %s:%d — got %s"
            % (rule, path, want[1], sorted(got)))
    assert not report.ok
    # attribution: the finding names the fixture file itself
    assert all(d.path.endswith("%s_seed.py" % rule.lower())
               for d in report.diagnostics)


def test_seed_defect_cli_exits_1():
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "threadlint.py"),
         "--seed-defect", "cc101"],
        capture_output=True, text=True, cwd=_ROOT)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "seeded defect detected: CC101" in out.stdout
    assert "cc101_seed.py:" in out.stdout


# -- whole-package clean run ------------------------------------------------


def test_package_clean_with_waivers_accounted():
    report = analyze_paths([_PKG])
    unwaived = [d for d in report.diagnostics
                if not d.waived and d.severity != "info"]
    assert report.ok, "\n".join(d.format() for d in unwaived)
    # the shipped tree's reviewed waiver list: every waiver is CC102 with
    # a non-empty justification, confined to the two blocking-by-design
    # critical sections (native one-shot build, decode step-under-cond)
    waived = report.waived
    assert waived, "expected the reviewed waiver list to be in effect"
    for d in waived:
        assert d.rule == "CC102"
        assert d.waive_reason
        assert ("native/__init__.py" in d.path.replace(os.sep, "/")
                or "serving/engine.py" in d.path.replace(os.sep, "/"))


def test_cli_clean_tree_exits_0():
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "threadlint.py"),
         "--dump", "json"],
        capture_output=True, text=True, cwd=_ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["ok"] is True
    assert sum(1 for f in doc["findings"] if f["waived"]) >= 1
    assert doc["unused_waivers"] == []


# -- waiver syntax ----------------------------------------------------------


def test_waiver_downgrades_and_is_counted():
    # cc102_seed.py ships one unwaived sleep and one waived sibling
    report = analyze_paths([_fixture("cc102")])
    waived = [d for d in report.diagnostics if d.waived]
    live = [d for d in report.diagnostics if not d.waived]
    assert len(waived) == 1
    assert waived[0].rule == "CC102"
    assert "demonstrates waiver syntax" in waived[0].waive_reason
    assert live and all(d.rule == "CC102" for d in live)
    # waived findings leave errors/warnings (and .ok) but stay reported
    assert all(d not in report.warnings for d in waived)
    assert "waiver" in report.format()


def test_unused_waiver_surfaces_as_note(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent("""\
        import threading

        _lock = threading.Lock()


        def fine():
            x = 1  # threadlint: waive CC102 nothing blocks here
            return x
        """))
    report = analyze_paths([str(p)])
    assert report.ok
    assert any(rule == "CC102" and line == 7
               for _path, line, rule, _reason in report.unused_waivers), \
        report.format()
    assert "unused waiver" in report.format()


# -- CC101 cycle detection (no declared order needed) -----------------------


def test_cc101_cycle_between_two_classes(tmp_path):
    p = tmp_path / "cyc.py"
    p.write_text(textwrap.dedent("""\
        import threading


        class A:
            def __init__(self, b):
                self._lock = threading.Lock()
                self.b = b

            def fwd(self):
                with self._lock:
                    self.b.take_b()

            def take_a(self):
                with self._lock:
                    pass


        class B:
            def __init__(self, a):
                self._lock = threading.Lock()
                self.a = a

            def take_b(self):
                with self._lock:
                    pass

            def back(self):
                with self._lock:
                    self.a.take_a()
        """))
    report = analyze_paths([str(p)])
    cc101 = [d for d in report.diagnostics if d.rule == "CC101"]
    assert cc101, report.format()
    assert any("A._lock" in d.message and "B._lock" in d.message
               for d in cc101)


def test_declared_lock_order_inversion(tmp_path):
    p = tmp_path / "ord.py"
    p.write_text(textwrap.dedent("""\
        import threading

        LOCK_ORDER = (("Outer._lock", "Inner._lock"),)


        class Inner:
            def __init__(self, outer):
                self._lock = threading.Lock()
                self.outer = outer

            def bad(self):
                with self._lock:
                    self.outer.touch()


        class Outer:
            def __init__(self):
                self._lock = threading.Lock()

            def touch(self):
                with self._lock:
                    pass
        """))
    report = analyze_paths([str(p)])
    assert any(d.rule == "CC101" and "LOCK_ORDER" in d.message
               for d in report.diagnostics), report.format()


# -- telemetry --------------------------------------------------------------


def test_threadlint_telemetry_counters():
    with _flags(telemetry=True):
        telemetry.reset()
        report_telemetry(analyze_paths([_fixture("cc102")]))
        snap = telemetry.snapshot()
    telemetry.reset()
    counters = snap.get("counters", {})
    assert counters.get(
        "static_check_concurrency_total{rule=CC102}", 0) >= 1
    assert counters.get(
        "static_check_waivers_total{rule=CC102}", 0) >= 1


def test_adoption_module_now_lints_clean():
    report = analyze_paths(
        [os.path.join(_PKG, "pallas_kernels", "adoption.py")])
    assert report.ok, report.format()
    assert not report.waived
