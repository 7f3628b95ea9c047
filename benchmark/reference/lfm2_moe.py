"""Plain reference for ``Lfm2MoeForCausalLM`` (LiquidAI LFM2-8B-A1B):
whole sequences, no state, no cache, no kernels.  (The program computes
a flat batch of ragged chunks and keeps each convolution's last two
gated rows a sequence between steps: different arithmetic for the same
function.)

One layer ``l``, all norms RMS::

    h = x + Op_l(norm(x));   y = h + FF_l(norm(h))

``Op_l`` by ``layer_types[l]``:

- ``conv``: ``[B, C, X] = W_in · u`` (three hidden-wide parts, in that
  order); ``z = B ⊙ X``; ``c_t = w_0 ⊙ z_{t-2} + w_1 ⊙ z_{t-1} + w_2 ⊙
  z_t`` with ``z_{<0} = 0``: a depthwise causal convolution of kernel 3
  over the sequence padded with two zero rows in front, one weight
  triple a channel, no bias, no activation; ``out = W_out · (C ⊙ c)``.
- ``full_attention``: ``q, k, v`` projections; RMS norm over each
  head's lanes of ``q`` and of ``k``; RoPE by half-rotation over the
  whole head; causal softmax of ``q·k · d^-½``, ``num_attention_heads /
  num_key_value_heads`` query heads a KV head, a block of queries at a
  time; ``W_o``.  The head size is ``hidden_size / num_attention_heads``
  (the published file has no ``head_dim``).

``FF_l`` is the dense SwiGLU FFN ``W_2(silu(W_1 x) ⊙ W_3 x)`` for ``l <
num_dense_layers`` and the routed layer after: ``s = sigmoid(W_g · x)``
over ``num_experts`` outputs; the ``num_experts_per_tok`` indices are
the top of ``s + b`` (``b`` = ``expert_bias``); the weights ``s`` at
those indices over their sum + 1e-6 (``norm_topk_prob``), times
``routed_scaling_factor``; the experts a SwiGLU FFN each at
``moe_intermediate_size``; no shared expert.  Then the final norm and a
head tied to the embedding.

Departures from the published code (`transformers` ``modeling_lfm2_moe``:
``Lfm2MoeShortConv``, ``Lfm2MoeSparseMoeBlock``), none in the function
computed: norm gains are ones (the seeded weights'); the published
convolution is a ``Conv1d`` with ``groups = hidden`` and padding
``L - 1`` cut back to the sequence's length, which is the sum over
three shifted copies here; every expert runs on every position and the
combine matrix is zero outside the top (a ``lax.scan`` over the stacked
experts, so that the check compiles one expert's products and not
thirty-two); the published code computes in bfloat16 where this is
float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common

ARCHITECTURE = "Lfm2MoeForCausalLM"
KEYS_PER_LAYER = 9
# seeded draws that are not ``normal × initializer_range`` (the
# program's models/lfm2_moe.py states the same two numbers)
EXPERT_BIAS_STD = 0.1
CONV_TAP_STD = 3.0**-0.5


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def make_weights(cfg: dict, seed: int, bits: int = 16) -> dict:
    """The program's recipe (models/lfm2_moe.py ``init_params``): one
    root key from the seed split into ``9 · layers + 1``; layer ``i``
    takes keys ``9i ..``: the operator's four (convolution: in_proj,
    taps, out_proj, one unused; attention: wq, wk, wv, wo), then the
    feed-forward's five (dense: gate, up, down, two unused; routed:
    router, bias, w1, w3, w2, experts stacked on the leading axis); the
    last key is the embedding's, and the head is its transpose.  Every
    matrix ``normal × initializer_range`` rounded to ``torch_dtype``,
    the taps ``normal × 3^-½`` rounded likewise, the router's bias
    ``normal × 0.1`` in float32.  ``bits=4`` stores the matrices the
    program may quantize (not the router, its bias, the taps or the
    embedding) as the int4 control."""
    h, nh, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    d, v = head_dim(cfg), cfg["vocab_size"]
    im, mim, e = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                  cfg["num_experts"])
    std, dt = cfg["initializer_range"], cfg["torch_dtype"]
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"], "layer_types vs depth"
    plain = common.normal_stored

    def store(key, shape):
        if bits == 16:
            return plain(key, shape, std, dt)
        return common.STORE[bits](key, shape, std, dt)

    keys = jax.random.split(common.root_key(seed),
                            KEYS_PER_LAYER * len(kinds) + 1)
    layers = []
    for i, kind in enumerate(kinds):
        k = keys[KEYS_PER_LAYER * i:]
        if kind == "conv":
            layer = {
                "in_proj": store(k[0], (h, 3 * h)),
                "conv_w": plain(k[1], (3, h), CONV_TAP_STD, dt),
                "out_proj": store(k[2], (h, h)),
            }
        elif kind == "full_attention":
            layer = {
                "wq": store(k[0], (h, nh * d)),
                "wk": store(k[1], (h, nkv * d)),
                "wv": store(k[2], (h, nkv * d)),
                "wo": store(k[3], (nh * d, h)),
            }
        else:
            raise ValueError(f"layer_types entry {kind!r}")
        if i < cfg["num_dense_layers"]:
            layer.update(gate=store(k[4], (h, im)), up=store(k[5], (h, im)),
                         down=store(k[6], (im, h)))
        else:
            layer.update(
                router=plain(k[4], (h, e), std, dt),
                expert_bias=plain(k[5], (e,), EXPERT_BIAS_STD, "float32"),
                w1=store(k[6], (e, h, mim)),
                w3=store(k[7], (e, h, mim)),
                w2=store(k[8], (e, mim, h)),
            )
        layers.append(layer)
    embed = plain(keys[-1], (v, h), std, dt)
    return {"embed": embed, "lm_head": embed.T, "layers": layers}


def short_conv(u: jax.Array, w: dict) -> jax.Array:
    """The gated short convolution over one whole sequence.  u: [T,
    hidden] (normed)."""
    t, h = u.shape
    bcx = u @ common.deq(w["in_proj"])
    b, c, x = bcx[:, :h], bcx[:, h: 2 * h], bcx[:, 2 * h:]
    z = jnp.pad(b * x, ((2, 0), (0, 0)))  # two zero rows before position 0
    taps = common.deq(w["conv_w"])
    conv = taps[0] * z[:t] + taps[1] * z[1: t + 1] + taps[2] * z[2:]
    return (c * conv) @ common.deq(w["out_proj"])


def attention(u: jax.Array, w: dict, cfg: dict) -> jax.Array:
    """Grouped-query causal attention with per-head q/k norms over one
    whole sequence.  u: [T, hidden] (normed)."""
    t = u.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    deq = common.deq
    q = common.rms_norm((u @ deq(w["wq"])).reshape(t, nh, d), eps)
    k = common.rms_norm((u @ deq(w["wk"])).reshape(t, nkv, d), eps)
    v = (u @ deq(w["wv"])).reshape(t, nkv, d)
    q = common.rope(q, theta).reshape(t, nkv, nh // nkv, d)
    k = common.rope(k, theta)
    outs = []
    for lo in range(0, t, common.QUERY_BLOCK):
        hi = min(lo + common.QUERY_BLOCK, t)
        s = jnp.einsum("qkgd,tkd->kgqt", q[lo:hi], k) * (d**-0.5)
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p, v))
    return jnp.concatenate(outs, 0).reshape(t, nh * d) @ deq(w["wo"])


def ffn(u, w: dict):
    deq = common.deq
    return (jax.nn.silu(u @ deq(w["gate"])) * (u @ deq(w["up"]))) @ deq(
        w["down"])


def route(u, w: dict, cfg: dict):
    """(indices [T, k], weights [T, k]): the bias moves the choice and
    never a weight; the weights are renormalised over the chosen."""
    scores = jax.nn.sigmoid(u @ common.deq(w["router"]))
    chosen_by = scores
    if cfg.get("use_expert_bias", True):
        chosen_by = scores + w["expert_bias"]
    _, idx = jax.lax.top_k(chosen_by, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return idx, weights * cfg.get("routed_scaling_factor", 1.0)


def moe(u, w: dict, cfg: dict):
    """The routed layer: every expert on every position, one at a time
    (a scan over the stacked experts), weighted by a combine matrix
    that is zero outside the top."""
    idx, weights = route(u, w, cfg)
    combine = jnp.zeros((u.shape[0], cfg["num_experts"]), jnp.float32).at[
        jnp.arange(u.shape[0])[:, None], idx].add(weights)

    def one(out, expert):
        w1, w3, w2, col = expert
        w1, w3, w2 = common.deq(w1), common.deq(w3), common.deq(w2)
        y = (jax.nn.silu(u @ w1) * (u @ w3)) @ w2
        return out + col[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u), (w["w1"], w["w3"], w["w2"], combine.T))
    return out


def _layer(x, w, cfg: dict):
    """One layer; which operator and which feed-forward it has is read
    from the tensors it holds."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["norm_eps"]
        u = common.rms_norm(x, eps)
        if "in_proj" in w:
            h = x + short_conv(u, w)
        else:
            h = x + attention(u, w, cfg)
        u = common.rms_norm(h, eps)
        return h + (ffn(u, w) if "gate" in w else moe(u, w, cfg))


def logprobs(weights: dict, cfg: dict, tokens, positions):
    # ``common`` reads the final norm's eps under Llama's name
    cfg = dict(cfg, rms_norm_eps=cfg["norm_eps"])
    return common.run_layers(
        common.jitted_layer(_layer, cfg), weights, cfg, tokens, positions)
