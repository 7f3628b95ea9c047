"""What a served step says of itself, by architecture, held to a golden
set (ISSUE 52): the attribute names on ``runner.dispatch`` and
``runner.fetch`` of a chunked single step, of a single step of one-token
rows and of a fused scan, and the labelled series of the worker's
counters on ``/metrics``.  The sets were written down from a run of
commit 7be2467 (PR 51), before the runner's counting helpers moved to
the models and the attention functions: a model or a kernel that stops
saying something, or says something new, fails here by name."""

import os
import re
from unittest import mock

import numpy as np
import pytest

from vllm_distributed_tpu import testing
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.tracing import get_tracer

# every dispatch span, whatever the model or the program
_DISPATCH = {"step_id", "program", "first_call", "overlapped", "seqs",
             "tokens", "kv_tokens"}
_MLA_PICKS = {"moe_picks_zero", "moe_picks_held", "moe_picks_absent"}
_DSV3_PICKS = {"moe_picks_held", "moe_picks_absent", "moe_picks_busiest",
               "moe_picks_skipped"}
_STATE = {"state_rows", "state_fresh"}

# architecture -> (config writer, its sizes, engine arguments, golden)
GOLDEN = {
    "mixtral": (testing.write_mixtral_config, {}, {"quantization": "int8"}, {
        "dispatch_chunked": _DISPATCH | {"moe", "q_tiles", "q_rows"},
        "dispatch_rows": _DISPATCH | {"moe", "q_tiles", "q_rows"},
        "dispatch_scan": _DISPATCH | {"moe"},
        "fetch_step": {"step_id"},
        "fetch_scan": {"step_id"},
        "series": {
            ("vllm:xla_compiles_total", "kind", "prefill"),
            ("vllm:xla_compiles_total", "kind", "decode"),
        },
    }),
    "longcat": (testing.write_longcat_config, {"layers": 1}, {}, {
        "dispatch_chunked": _DISPATCH | {
            "q_tiles", "q_rows", "chunk_tokens_fresh",
            "chunk_tokens_prefixed"},
        "dispatch_rows": _DISPATCH | {"q_tiles", "q_rows"},
        "dispatch_scan": _DISPATCH | {"kv_rows_fetched"},
        "fetch_step": {"step_id"} | _MLA_PICKS,
        "fetch_scan": {"step_id"} | _MLA_PICKS,
        "series": {
            ("vllm:xla_compiles_total", "kind", "prefill"),
            ("vllm:xla_compiles_total", "kind", "decode"),
            ("vllm:moe_picks_total", "kind", "zero"),
            ("vllm:moe_picks_total", "kind", "held"),
            ("vllm:moe_picks_total", "kind", "absent"),
            ("vllm:mla_chunk_tokens_total", "form", "materialised"),
            ("vllm:mla_chunk_tokens_total", "form", "absorbed"),
            ("vllm:mla_kv_rows_total", "kind", "live"),
            ("vllm:mla_kv_rows_total", "kind", "fetched"),
        },
    }),
    "lfm2": (testing.write_lfm2_config, {}, {}, {
        "dispatch_chunked": _DISPATCH | _STATE | {"moe", "q_tiles", "q_rows"},
        "dispatch_rows": _DISPATCH | _STATE | {"moe", "q_tiles", "q_rows"},
        "dispatch_scan": _DISPATCH | _STATE | {"moe"},
        "fetch_step": {"step_id", "moe_picks_all", "moe_picks_busiest"},
        "fetch_scan": {"step_id", "moe_picks_all", "moe_picks_busiest"},
        "series": {
            ("vllm:xla_compiles_total", "kind", "prefill"),
            ("vllm:xla_compiles_total", "kind", "decode"),
            ("vllm:moe_expert_picks_total", "kind", "all"),
            ("vllm:moe_expert_picks_total", "kind", "busiest"),
            ("vllm:seq_state_rows_total", "kind", "fresh"),
            ("vllm:seq_state_rows_total", "kind", "carried"),
            ("vllm:seq_state_bytes", "kind", "conv"),
        },
    }),
    "deepseek_v3": (testing.write_deepseek_v3_config, {}, {}, {
        "dispatch_chunked": _DISPATCH | {
            "moe", "q_tiles", "q_rows", "chunk_tokens_fresh",
            "chunk_tokens_prefixed"},
        "dispatch_rows": _DISPATCH | {"moe", "q_tiles", "q_rows"},
        "dispatch_scan": _DISPATCH | {"moe", "kv_rows_fetched"},
        "fetch_step": {"step_id"} | _DSV3_PICKS,
        "fetch_scan": {"step_id"} | _DSV3_PICKS,
        "series": {
            ("vllm:xla_compiles_total", "kind", "prefill"),
            ("vllm:xla_compiles_total", "kind", "decode"),
            ("vllm:moe_picks_total", "kind", "held"),
            ("vllm:moe_picks_total", "kind", "absent"),
            ("vllm:moe_picks_total", "kind", "busiest"),
            ("vllm:moe_picks_total", "kind", "skipped"),
            ("vllm:mla_chunk_tokens_total", "form", "materialised"),
            ("vllm:mla_chunk_tokens_total", "form", "absorbed"),
            ("vllm:mla_kv_rows_total", "kind", "live"),
            ("vllm:mla_kv_rows_total", "kind", "fetched"),
        },
    }),
    "falcon_h1": (testing.write_falcon_h1_config, {}, {}, {
        "dispatch_chunked": _DISPATCH | _STATE | {
            "q_tiles", "q_rows", "ssm_tokens_live", "ssm_tokens_tiled"},
        "dispatch_rows": _DISPATCH | _STATE | {"q_tiles", "q_rows"},
        "dispatch_scan": _DISPATCH | _STATE,
        "fetch_step": {"step_id"},
        "fetch_scan": {"step_id"},
        "series": {
            ("vllm:xla_compiles_total", "kind", "prefill"),
            ("vllm:xla_compiles_total", "kind", "decode"),
            ("vllm:seq_state_rows_total", "kind", "fresh"),
            ("vllm:seq_state_rows_total", "kind", "carried"),
            ("vllm:seq_state_bytes", "kind", "conv"),
            ("vllm:seq_state_bytes", "kind", "ssm"),
            ("vllm:ssm_chunk_tokens_total", "kind", "live"),
            ("vllm:ssm_chunk_tokens_total", "kind", "tiled"),
        },
    }),
}
# The worker's labelled families.  Those of the last three count what
# the process's compile cache did, which is not the model's to say: the
# test holds their label values to these, not to a set.
_FAMILIES = {
    "vllm:xla_compiles_total", "vllm:worker_fallbacks_total",
    "vllm:moe_picks_total", "vllm:moe_expert_picks_total",
    "vllm:seq_state_rows_total", "vllm:seq_state_bytes",
    "vllm:ssm_chunk_tokens_total", "vllm:mla_chunk_tokens_total",
    "vllm:mla_kv_rows_total",
}
_PROCESS_FAMILIES = {
    "vllm:xla_persistent_cache_events_total": {"lookups", "hits", "writes"},
    "vllm:xla_backend_compiles_total": {"compiled", "cache"},
    "vllm:xla_backend_compile_seconds_total": {"compiled", "cache"},
}
_SERIES = re.compile(r'^(vllm:\w+)\{(.*)\} \S+$')


def _names(spans):
    """The distinct sets of attribute names among ``spans``."""
    return {frozenset(s["attributes"]) for s in spans}


def serve(arch: str, dirname: str | None = None) -> dict:
    """A short mixed run of ``arch``'s tiny configuration under the
    interpreted kernels: three prompts against a 32-token budget (chunks
    with and without a cached prefix, one-token rows beside them), then
    fused scans of four; then one request that reads logprobs, served
    in single steps of one-token rows."""
    write, sizes, engine_args, _ = GOLDEN[arch]
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(3, 128, n))) for n in (70, 5, 37)]
    tracer = get_tracer()
    tracer.reset()
    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": "pallas_interpret"}):
        engine = LLMEngine.from_engine_args(EngineArgs(
            model=write(dirname, **sizes), skip_tokenizer_init=True,
            num_kv_pages=64, max_model_len=256, max_num_seqs=8,
            num_decode_steps=4, max_num_batched_tokens=32,
            load_format="dummy", seed=7, enable_tracing=True,
            **engine_args))
        try:
            for i, p in enumerate(prompts):
                engine.add_request(
                    f"r{i}", prompt_token_ids=p,
                    sampling_params=SamplingParams(
                        temperature=0.0, max_tokens=11, ignore_eos=True))
            while engine.has_unfinished_requests():
                engine.step()
            # a request that reads logprobs is not fused: single steps
            # of one one-token row
            engine.add_request(
                "lp", prompt_token_ids=prompts[1],
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=3, ignore_eos=True,
                    logprobs=1))
            while engine.has_unfinished_requests():
                engine.step()
            engine.refresh_device_telemetry()
            text = engine.metrics.render().decode()
            spans = [s for tr in tracer.snapshot() for s in tr["spans"]]
        finally:
            engine.shutdown()
            tracer.configure(False)
            tracer.reset()
    dispatch = [s for s in spans if s["name"] == "runner.dispatch"]
    scan_steps = {s["attributes"]["step_id"] for s in dispatch
                  if s["attributes"]["program"].startswith("vdt_decode_")}
    steps = [s for s in dispatch
             if s["attributes"]["program"].startswith("vdt_step_")]
    fetch = [s for s in spans if s["name"] == "runner.fetch"]
    series, process_series = set(), set()
    for line in text.splitlines():
        m = _SERIES.match(line)
        if not m:
            continue
        labels = dict(re.findall(r'(\w+)="([^"]*)"', m.group(2)))
        labels.pop("model_name")
        if m.group(1) in _FAMILIES:
            series.update((m.group(1), k, v) for k, v in labels.items())
        elif m.group(1) in _PROCESS_FAMILIES:
            process_series.update(
                (m.group(1), v) for v in labels.values())
    return {
        "dispatch_chunked": _names(
            s for s in steps if "_q1_" not in s["attributes"]["program"]),
        "dispatch_rows": _names(
            s for s in steps if "_q1_" in s["attributes"]["program"]),
        "dispatch_scan": _names(
            s for s in dispatch if s["attributes"]["step_id"] in scan_steps),
        "fetch_step": _names(
            s for s in fetch if s["attributes"]["step_id"] not in scan_steps),
        "fetch_scan": _names(
            s for s in fetch if s["attributes"]["step_id"] in scan_steps),
        "series": series,
        "process_series": process_series,
        "families": set(re.findall(r"^# TYPE (vllm:\S+) ", text, flags=re.M)),
    }


@pytest.mark.parametrize("arch", sorted(GOLDEN))
def test_a_served_step_says_what_it_said_at_the_parent(arch, tmp_path):
    golden = GOLDEN[arch][3]
    got = serve(arch, str(tmp_path))
    for kind in ("dispatch_chunked", "dispatch_rows", "dispatch_scan",
                 "fetch_step", "fetch_scan"):
        # every span of the kind carries exactly the golden names
        assert got[kind] == {frozenset(golden[kind])}, (arch, kind, got[kind])
    assert got["series"] == golden["series"], arch
    assert (_FAMILIES | set(_PROCESS_FAMILIES)) <= got["families"]
    assert "vllm:moe_grouped_steps_total" in got["families"]
    for family, value in got["process_series"]:
        assert value in _PROCESS_FAMILIES[family], (family, value)


if __name__ == "__main__":  # print what this tree serves, to write down
    import pprint
    import sys

    for name in sys.argv[1:] or sorted(GOLDEN):
        got = serve(name)
        del got["families"]
        pprint.pprint({name: got})
