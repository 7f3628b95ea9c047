"""``benchmark/run.py`` as the driver starts it: with no chip it exits
non-zero and prints no result; on the CPU rehearsal it drives every
stage — server child, warm-up by traffic, window, logprob requests,
plain reference, per-layer readers — and reports counts, never a
metric."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = [sys.executable, os.path.join("benchmark", "run.py")]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("VDT_USE_PALLAS", None)  # the cell's own file names the backend
    return env


def test_no_chip_is_an_error_and_prints_no_result():
    cell = json.load(open(os.path.join(REPO, "BENCHMARK.json")))[
        "workloads"][0]["name"]
    p = subprocess.run(
        RUN + ["--workload", cell, "--seed", "3000000001",
               "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "need a TPU" in p.stderr
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


def test_checkout_without_the_program_is_an_error(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        RUN + ["--workload", "x", "--seed", "1", "--seconds", "1",
               "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell,trace", [("tiny.closed", 1), ("tiny.closed", 0)])
def test_cpu_rehearsal_drives_every_stage(cell, trace):
    p = subprocess.run(
        RUN + ["--rehearsal", "--workload", cell, "--seed", "3000000019",
               "--seconds", "3", "--trace", str(trace)],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["not_a_result"] == "cpu rehearsal"
    assert "metrics" not in last and "device" not in last
    assert last["readings"]["values"]["n"] > 0
    assert last["readings"]["tokens"]["n"] > 0
    if trace:
        assert {"loadgen_late_p95_ms", "queue_wait_p95_ms",
                "decode_batch_mean", "window_compiles",
                "programs_compiled"} <= set(last["counts"])
    # every number compared is printed beside its limit, in every run
    assert "check: values.p90 = " in p.stdout and "(limit " in p.stdout
    assert "check_s = " in p.stdout
