"""Tier-1 lint gate: a thin driver over tools/vdt_lint (ISSUE 6).

The two original AST checks that lived here (silent broad excepts in
distributed/ — ISSUE 2 satellite; orphanable manual start_span —
ISSUE 5 satellite) are now VDT006/VDT007 in the framework, alongside
five more checkers encoding the engine's concurrency, registry, and
failure-handling invariants.  This file just runs the whole catalog
over the package — one shared parse pass per file — and fails on any
new unwaived, un-baselined finding, printing rule id and file:line.

Checker unit tests (fixture corpus, waiver/baseline round-trips, CLI)
live in tests/test_vdt_lint.py.
"""

import ast
import pathlib
import re

import pytest

from tools.vdt_lint import (
    DEFAULT_BASELINE_PATH,
    load_baseline,
    run_lint,
)

pytestmark = pytest.mark.lint


def test_package_has_no_new_findings():
    report = run_lint()
    assert not report.new, (
        "new vdt-lint findings (fix, or waive at the site with "
        "`# vdt-lint: disable=<rule>` plus a justification):\n"
        + "\n".join(f.render() for f in report.new)
    )


def test_control_plane_carries_no_baseline_debt():
    """ISSUE 6 satellite (extended by ISSUE 7 to worker/ and ISSUE 10
    to router/): the committed baseline must stay empty for
    distributed/, executor/, worker/, and router/ — control-plane and
    run-loop findings are fixed or waived with a justification at the
    site, never grandfathered."""
    entries = load_baseline(DEFAULT_BASELINE_PATH)
    offenders = [
        e
        for e in entries
        if "/distributed/" in e.get("path", "")
        or "/executor/" in e.get("path", "")
        or "/worker/" in e.get("path", "")
        or "/router/" in e.get("path", "")
    ]
    assert not offenders, offenders


def test_every_pallas_call_states_a_unique_literal_vdt_name():
    """PR 26: a Pallas kernel without ``name=`` takes whatever stands
    innermost on its name stack as its instruction name (``flat_fn``,
    ``closed_call``), so a profile cannot tell one kernel from another.
    Every ``pl.pallas_call`` under ``ops/pallas/`` states a literal
    ``name="vdt_…"``, and no two state the same."""
    pkg = pathlib.Path(__file__).resolve().parents[1] / "vllm_distributed_tpu"
    names = []
    for path in sorted((pkg / "ops" / "pallas").glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                continue
            kw = {k.arg: k.value for k in node.keywords}
            where = f"{path.name}:{node.lineno}"
            assert "name" in kw, f"{where}: pallas_call without name="
            assert isinstance(kw["name"], ast.Constant) and isinstance(
                kw["name"].value, str), f"{where}: name= is not a literal"
            assert re.match(r"^vdt_[a-z0-9_]+$", kw["name"].value), where
            names.append(kw["name"].value)
    assert len(names) >= 4 and len(set(names)) == len(names), names
