"""Each plain reference against the program's own model at tiny widths
in float32: prefill, then decoding through the paged cache, against the
reference's one full forward pass; and the check's statistic on an
honest run and on an int4 one."""

import json

import numpy as np
import pytest

from benchmark import check
from benchmark.reference import mixtral

SEED = 20250927
PROMPT_LENS = (5, 37, 70)


def _serve(tmp_path, arch, quant, dtype="float32"):
    from vllm_distributed_tpu import testing
    from vllm_distributed_tpu.entrypoints.llm import LLM
    from vllm_distributed_tpu.sampling_params import SamplingParams

    sizes = dict(vocab_size=512, hidden=64, intermediate=128, layers=2,
                 heads=8, kv_heads=4, dtype=dtype)
    if arch == "mixtral":
        d = testing.write_mixtral_config(str(tmp_path), num_experts=4, **sizes)
    else:
        d = testing.write_llama_config(str(tmp_path), **sizes)
    path = f"{d}/config.json"
    hf = json.load(open(path))
    if arch == "mistral":
        hf.update(architectures=["MistralForCausalLM"], model_type="mistral")
        json.dump(hf, open(path, "w"))
    llm = LLM(model=d, skip_tokenizer_init=True, load_format="dummy",
              quantization=quant, seed=SEED, num_kv_pages=64,
              max_model_len=256)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(3, 512, n))) for n in PROMPT_LENS]
    outs = llm.generate(
        prompt_token_ids=prompts,
        sampling_params=SamplingParams(
            temperature=0, max_tokens=12, ignore_eos=True, logprobs=3))
    runs = [{"kind": "overlap", "prompt": p,
             "tokens": list(o.outputs[0].token_ids),
             "top": [dict(d) for d in o.outputs[0].logprobs],
             "token_logprobs": [d[t] for t, d in zip(
                 o.outputs[0].token_ids, o.outputs[0].logprobs)]}
            for p, o in zip(prompts, outs)]
    return dict(hf, initializer_range=0.02), runs


def _evaluate(ref, cfg, runs, limits):
    weights = ref.make_weights(cfg, SEED)
    window = [{"index": i, "prompt": r["prompt"], "tokens": r["tokens"]}
              for i, r in enumerate(runs)]
    return check.evaluate(
        lambda toks, pos: ref.logprobs(weights, cfg, toks, pos),
        runs, window, limits)


LIMITS = {"values": {"p90": 1e-5, "mean": 1e-5},
          "tokens": {"p99": 1e-5, "mean": 1e-5}}


@pytest.mark.parametrize("arch,ref", [("mixtral", mixtral)])
def test_reference_agrees_with_the_program_in_float32(tmp_path, arch, ref):
    cfg, runs = _serve(tmp_path, arch, "int8")
    res = _evaluate(ref, cfg, runs, LIMITS)
    assert res["correct"], res["compared"]
    assert res["readings"]["values"]["n"] >= 3 * 12
    assert res["readings"]["values"]["max"] < 1e-5
    assert res["readings"]["tokens"]["max"] == 0.0


@pytest.mark.parametrize("arch,ref", [("mixtral", mixtral)])
def test_int4_control_fails_the_check(tmp_path, arch, ref):
    cfg, runs = _serve(tmp_path, arch, "int4")
    res = _evaluate(ref, cfg, runs, LIMITS)
    assert not res["correct"]
    failed = {(c["part"], c["stat"]) for c in res["compared"] if not c["ok"]}
    assert ("values", "p90") in failed and ("values", "mean") in failed
    assert res["readings"]["values"]["p50"] > 100 * LIMITS["values"]["p90"]
    assert res["offending"]["values"][0]["kind"] == "overlap"


def test_seeded_weights_are_the_bytes_the_programs_loader_makes(tmp_path):
    """The reference draws its weights from the seed by the recipe the
    configuration states; this is the test that the recipe is the
    program's (two loads, the same bytes)."""
    import jax

    from vllm_distributed_tpu import testing
    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.model_loader.loader import get_model

    d = testing.write_mixtral_config(
        str(tmp_path), vocab_size=512, hidden=64, intermediate=128,
        layers=2, heads=8, kv_heads=4, num_experts=4, dtype="bfloat16")
    hf = json.load(open(f"{d}/config.json"))
    ec = EngineArgs(model=d, skip_tokenizer_init=True, load_format="dummy",
                    quantization="int8", seed=SEED).create_engine_config()
    _, params = get_model(ec.model_config, load_format="dummy")
    ours = mixtral.make_weights(dict(hf, initializer_range=0.02), SEED)
    again = mixtral.make_weights(dict(hf, initializer_range=0.02), SEED)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(ours), jax.tree.leaves(again)))
    np.testing.assert_array_equal(ours["embed"], params["embed"])
    np.testing.assert_array_equal(ours["lm_head"][0], params["lm_head"].q)
    np.testing.assert_array_equal(ours["lm_head"][1], params["lm_head"].scale)
    for mine, theirs in zip(ours["layers"], params["layers"]):
        np.testing.assert_array_equal(mine["router"], theirs["router"])
        for name in ("wq", "wk", "wv", "wo", "w1", "w2", "w3"):
            np.testing.assert_array_equal(mine[name][0], theirs[name].q)
            np.testing.assert_array_equal(mine[name][1], theirs[name].scale)


def test_check_statistic_is_a_quantile_not_a_maximum():
    rows = np.log(np.full((4, 8), 1 / 8.0))
    run = {"kind": "alone", "prompt": [1, 2], "tokens": [3, 4, 5, 6],
           "top": [{3: rows[0, 3]}, {4: rows[1, 4]}, {5: rows[2, 5]},
                   {6: rows[3, 6] + 0.5}],  # one far-off position
           "token_logprobs": [rows[0, 3], rows[1, 4], rows[2, 5],
                              rows[3, 6] + 0.5]}
    res = check.evaluate(lambda toks, pos: rows, [run], [],
                         {"values": {"p50": 1e-6}})
    assert res["correct"] and res["readings"]["values"]["max"] == 0.5
    res = check.evaluate(lambda toks, pos: rows, [run], [],
                         {"values": {"p99": 1e-6}})
    assert not res["correct"]


@pytest.mark.parametrize("arch,ref", [("mixtral", mixtral)])
def test_reference_at_int4_in_the_programs_place_fails(tmp_path, arch, ref):
    """The control the chip calibration uses where the program's own
    int4 path cannot load at the cell's size: the reference itself, its
    weights stored as int4, scored exactly as the program is."""
    cfg, runs = _serve(tmp_path, arch, "int8")
    sound = ref.make_weights(cfg, SEED)
    low = ref.make_weights(cfg, SEED, bits=4)
    window = [{"index": i, "prompt": r["prompt"], "tokens": r["tokens"]}
              for i, r in enumerate(runs)]
    lp_rows, win_rows = check.score_all(
        lambda toks, pos: ref.logprobs(sound, cfg, toks, pos), runs, window)
    ctl_runs, ctl_window = check.control_in_place(
        lambda toks, pos: ref.logprobs(low, cfg, toks, pos), runs, window, 3)
    res = check.evaluate_rows(lp_rows, win_rows, ctl_runs, ctl_window, LIMITS)
    assert not res["correct"]
    assert res["readings"]["values"]["p50"] > 100 * LIMITS["values"]["p90"]
    # and the sound reference put in its own place reads exactly zero
    same_runs, same_window = check.control_in_place(
        lambda toks, pos: ref.logprobs(sound, cfg, toks, pos), runs, window, 3)
    res = check.evaluate_rows(
        lp_rows, win_rows, same_runs, same_window, LIMITS)
    assert res["correct"] and res["readings"]["values"]["max"] == 0.0
    assert res["readings"]["tokens"]["max"] == 0.0
