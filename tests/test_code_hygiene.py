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

_ROOT = pathlib.Path(__file__).resolve().parents[1]


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
    names = []
    pallas = _ROOT / "vllm_distributed_tpu" / "ops" / "pallas"
    for path in sorted(pallas.glob("*.py")):
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


def test_every_registered_env_var_has_a_reader_in_the_package():
    """A name in ``envs.environment_variables`` that nothing under
    ``vllm_distributed_tpu/`` reads is an option that sets nothing:
    each must be named somewhere in the package outside envs.py.  The
    Hugging Face variables are registered only to be replicated; the
    hub library reads them."""
    from vllm_distributed_tpu import envs

    source = "\n".join(
        p.read_text()
        for p in sorted((_ROOT / "vllm_distributed_tpu").rglob("*.py"))
        if p.name != "envs.py"
    )
    unread = [
        name
        for name in envs.environment_variables
        if name not in envs.ADDITIONAL_REPLICATED_ENV_VARS
        and not re.search(rf"\b{name}\b", source)
    ]
    assert not unread, f"registered in envs.py, read by nothing: {unread}"


_TREES = ("tools", "tests", "vllm_distributed_tpu", "benchmark")
# A path under one of the repo's trees, or a bare *.py / *.json / *.md
# name; not a placeholder in <angle brackets>, not the tail of a longer
# path or URL.
_CITED_PATH = re.compile(
    r"(?<![\w/.<\-])"
    rf"((?:{'|'.join(_TREES)})/[\w./\-]*\w"
    r"|[A-Za-z_][\w\-]*(?:\.[\w\-]+)*\.(?:py|json|md))"
    r"(?![\w/])"
)
# The reference deployment's launcher, cited as launch.py:<line>.
_NOT_OURS = {"launch.py"}


def test_every_file_the_readme_and_envs_comments_name_exists():
    """README.md and the comments of envs.py tell an operator which
    tool to run and which file holds what; a deleted file must take its
    citations with it.  A path with a directory must exist as written;
    a bare name must be a file at the root or somewhere in the repo's
    trees."""
    names = {p.name for p in _ROOT.iterdir() if p.is_file()}
    for tree in _TREES:
        names.update(p.name for p in (_ROOT / tree).rglob("*") if p.is_file())
    envs_comments = "\n".join(
        line
        for line in (_ROOT / "vllm_distributed_tpu" / "envs.py")
        .read_text().splitlines()
        if line.lstrip().startswith("#")
    )
    dangling = []
    for where, text in (
        ("README.md", (_ROOT / "README.md").read_text()),
        ("envs.py", envs_comments),
    ):
        for cited in sorted(set(_CITED_PATH.findall(text)) - _NOT_OURS):
            found = (
                (_ROOT / cited).exists() if "/" in cited else cited in names
            )
            if not found:
                dangling.append(f"{where}: {cited}")
    assert not dangling, "files cited that are not in the tree:\n" + "\n".join(
        dangling
    )


def _identifiers(tree: ast.AST) -> set[str]:
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
        elif isinstance(node, ast.arg):
            names.add(node.arg)
        elif isinstance(node, ast.keyword) and node.arg:
            names.add(node.arg)
        elif isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
    return names


def test_the_runner_and_the_engine_know_no_worker_counter_by_name():
    """ISSUE 52: a worker counter is a row of ``metrics.WORKER_COUNTERS``
    and nothing else in the two files every architecture used to edit:
    no key of the table is an identifier, or part of one (a variable,
    an attribute, a method, a parameter: ``record_moe_picks``,
    ``_telemetry_moe_picks_seen``), in ``worker/model_runner.py`` or
    ``engine/llm_engine.py``; the runner's sizing of the state arrays
    it allocates is its own.  And what a step counts is said beside
    the kernels: the runner imports the attention functions from
    ``ops/pallas/{mla,paged}_attention`` and nothing else."""
    from vllm_distributed_tpu.metrics import WORKER_COUNTERS

    package = _ROOT / "vllm_distributed_tpu"
    runner = ast.parse((package / "worker" / "model_runner.py").read_text())
    engine = ast.parse((package / "engine" / "llm_engine.py").read_text())
    for where, tree in (("model_runner.py", runner), ("llm_engine.py", engine)):
        known = {
            name for name in _identifiers(tree) - {"_seq_state_bytes"}
            if any(key in name for key in WORKER_COUNTERS)
        }
        assert not known, f"{where} names worker counters: {sorted(known)}"
    allowed = {
        "mla_attention": {"mla_attention", "mla_attention_cpu"},
        "paged_attention": {"paged_attention", "paged_attention_cpu"},
    }
    imported = {}
    for node in ast.walk(runner):
        if isinstance(node, ast.ImportFrom) and node.module:
            head, _, module = node.module.rpartition(".")
            if head.endswith("ops.pallas") and module in allowed:
                imported.setdefault(module, set()).update(
                    a.name for a in node.names)
        elif isinstance(node, ast.Import):
            assert not any(
                a.name.endswith(tuple(f"ops.pallas.{m}" for m in allowed))
                for a in node.names), "import the attention functions by name"
    assert imported and all(
        names <= allowed[module] for module, names in imported.items()
    ), imported
