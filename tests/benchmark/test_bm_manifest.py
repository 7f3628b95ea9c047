"""Every entry of BENCHMARK.json resolves to files that exist, and the
benchmark is driven by data: a cell, a configuration or a per-layer
metric is added by new files and one manifest entry."""

import importlib
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _cells_reporting(metric: dict) -> list[str]:
    return metric.get("workloads") or [
        w["name"] for w in MANIFEST["workloads"]]


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert MANIFEST["command"][:2] == ["python3", "benchmark/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536


@pytest.mark.parametrize(
    "cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    conf = [c for c in MANIFEST["configs"] if c["name"] == cell["config"]]
    assert len(conf) == 1
    mix = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", cell["traffic"] + ".json")))
    for key in ("arrival", "prompt_len", "output_len", "pool_size",
                "warmup", "check"):
        assert key in mix
    assert {"flat_requests", "cap_s"} <= set(mix["warmup"])
    assert mix["arrival"]["process"] == "closed"
    sweep = mix["warmup"]["sweep"]
    assert sweep["background"] + sweep["seats"] == mix["arrival"]["clients"]


@pytest.mark.parametrize(
    "conf", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_resolves_to_files(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert conf["file"].startswith("benchmark/configs/")
    data = json.load(open(os.path.join(REPO, conf["file"])))
    bench = data["benchmark"]
    assert bench["source"] == conf["source"]
    assert set(bench["reduced"]) == set(conf["reduced"])
    for key in conf["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size"
                             r"|num_experts_per_tok)$", key)
    ref = importlib.import_module(
        "benchmark.reference." + bench["reference"])
    assert ref.ARCHITECTURE == data["architectures"][0]
    assert any(conf["name"] == w["config"] for w in MANIFEST["workloads"])
    # every limit of the check is a number with its calibration beside it
    for part in bench["check"].values():
        assert part and all(isinstance(v, float) for v in part.values())


@pytest.mark.parametrize(
    "metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    assert set(metric) - {"workloads"} == {
        "name", "unit", "better", "source", "layer", "moves"}
    reader = importlib.import_module(
        "benchmark.layer_metrics." + metric["name"].replace(".", "_"))
    assert callable(reader.read)
    moved = [m for m in MANIFEST["end_to_end"] if m["name"] == metric["moves"]]
    assert len(moved) == 1
    for cell in _cells_reporting(metric):
        assert cell in _cells_reporting(moved[0])


@pytest.mark.parametrize(
    "metric", MANIFEST["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric_is_well_formed(metric):
    assert set(metric) - {"workloads"} == {
        "name", "unit", "better", "bound", "source"}
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", metric["unit"])


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    names = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in names
    for cell in MANIFEST["workloads"]:
        e2e = [m for m in MANIFEST["end_to_end"]
               if cell["name"] in _cells_reporting(m)]
        assert len(e2e) >= 2
        assert any(cell["name"] in _cells_reporting(m)
                   for m in MANIFEST["per_layer"])


def test_nothing_under_benchmark_imports_the_programs_bench_code():
    for root, _, files in os.walk(os.path.join(REPO, "benchmark")):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(root, f)).read()
                assert "entrypoints.cli" not in text, f
                assert not re.search(r"^\s*(import|from) bench\b", text,
                                     re.M), f
