"""Plain reference for ``MixtralForCausalLM`` (Mixtral-8x7B-v0.1).

The Mistral decoder with the dense MLP replaced by a sparse mixture of
experts: router logits ``h @ Wg`` over all experts, softmax, the top
``num_experts_per_tok`` kept and renormalised to sum to one; each kept
expert is ``w2(silu(w1(h)) * w3(h))``.  Written the slow way: every
expert runs on every position and a [T, E] weight, zero outside the
top-k, combines them — one expert's float32 copy at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common

ARCHITECTURE = "MixtralForCausalLM"


def make_weights(cfg: dict, seed: int, bits: int = 8) -> dict:
    """The attention tensors, embedding and head as for Mistral (the
    dense MLP's three keys per layer are drawn and unused); then, from
    ``fold_in(root, 1)`` split into ``4 * layers`` keys, layer ``i``
    takes keys ``4i..4i+3`` for router, w1, w3, w2 (experts stacked on
    the leading axis).  The router is not quantized."""
    weights = common.base_weights(cfg, seed, ("wq", "wk", "wv", "wo"), bits)
    store = common.STORE[bits]
    n_layers = cfg["num_hidden_layers"]
    e, h = cfg["num_local_experts"], cfg["hidden_size"]
    im, std = cfg["intermediate_size"], cfg["initializer_range"]
    dt = cfg["torch_dtype"]
    keys = jax.random.split(
        jax.random.fold_in(common.root_key(seed), 1), 4 * n_layers
    )
    for i, layer in enumerate(weights["layers"]):
        layer["router"] = common.normal_stored(keys[4 * i], (h, e), std, dt)
        layer["w1"] = store(keys[4 * i + 1], (e, h, im), std, dt)
        layer["w3"] = store(keys[4 * i + 2], (e, h, im), std, dt)
        layer["w2"] = store(keys[4 * i + 3], (e, im, h), std, dt)
    return weights


def _moe(h, w, cfg: dict):
    n_exp, top_k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ common.deq(w["router"]), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    combine = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i
    ].set(top_w)
    out = jnp.zeros_like(h)
    for e in range(n_exp):
        w1, w3, w2 = (
            common.deq((w[n][0][e], w[n][1][e]))
            for n in ("w1", "w3", "w2"))
        y = (jax.nn.silu(h @ w1) * (h @ w3)) @ w2
        out = out + combine[:, e : e + 1] * y
    return out


def _layer(x, w, cfg: dict):
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        x = x + common.attention(common.rms_norm(x, eps), w, cfg)
        return x + _moe(common.rms_norm(x, eps), w, cfg)


def logprobs(weights: dict, cfg: dict, tokens, positions):
    return common.run_layers(
        common.jitted_layer(_layer, cfg), weights, cfg, tokens, positions)
