"""Plain reference for ``DeepseekV3ForCausalLM`` (the benchmark runs
GigaChat3.1-702B-A36B's sizes), in the **materialised** form: every
head's key and value are up-projected from the latent and attention is
ordinary causal softmax attention over them.  (The program computes the
absorbed form over its latent cache: different arithmetic for the same
function.)

One layer, with ``n`` RMS norms (eps ``rms_norm_eps``)::

    h = x + A(n(x));   y = h + F_l(n(h))

``F_l`` is a SwiGLU FFN of width ``intermediate_size`` for ``l <
first_k_dense_replace``, else ``F(u) = S(u) + R(u)``: ``S`` a SwiGLU of
width ``n_shared_experts · moe_intermediate_size``, ``R`` routed.

``A``: ``q = W_qb · n(W_qa · x)`` per head ``[q_nope | q_rope]``;
``[c | k_rope] = W_kva · x``, ``c = n(c)``, ``k_rope`` one vector for
all heads; RoPE on ``q_rope`` and ``k_rope`` with YaRN: per pair ``i``
of ``d = qk_rope_head_dim`` lanes, ``f_i = theta^(-2i/d)``; ``lo =
floor(d · ln(L0 / (beta_fast · 2π)) / (2 ln theta))``, ``hi = ceil(d ·
ln(L0 / (beta_slow · 2π)) / (2 ln theta))``, clamped to ``[0, d - 1]``;
``m_i = 1 - clip((i - lo) / (hi - lo), 0, 1)``; ``inv_freq_i = (f_i /
factor)(1 - m_i) + f_i m_i``; cos and sin times ``mscale(factor,
mscale) / mscale(factor, mscale_all_dim)`` with ``mscale(f, m) = 0.1 m
ln f + 1``; per head ``[k_nope | v] = W_kvb · c`` (``v_head_dim`` need
not equal ``qk_nope_head_dim``); scores ``(q_nope·k_nope + q_rope·
k_rope)`` × ``(nope + rope)^-½ · mscale(factor, mscale_all_dim)²``,
causal softmax, times ``v``, heads concatenated, ``W_o``.

``R``: float32 ``s = sigmoid(W_r · u)`` over ``n_routed_experts ×
expert_parallel_size`` outputs; ``s' = s + b``; ``n_group`` groups of
consecutive experts, a group's score the sum of its two largest ``s'``;
the ``topk_group`` best groups stay, every other expert's ``s'`` is
masked to 0; the indices are the top ``num_experts_per_tok`` of the
masked ``s'``; the weights ``s`` at those indices over their sum +
1e-20 (``norm_topk_prob``), times ``routed_scaling_factor``.  **The
share**: this chip holds ``n_routed_experts`` experts, those of rank
``expert_parallel_rank`` among ``expert_parallel_size`` chips; a pick of
one of them adds its product, a pick of an expert held elsewhere adds
nothing, ``S(u)`` is added whole — in the program and here alike.

Departures from the published code (`transformers`
``modeling_deepseek_v3``): one, which changes no score: RoPE rotates
interleaved pairs (lanes 2i, 2i+1) where the published code first moves
each pair's lanes to (i, i + d/2) and rotates halves, on the query and
the key alike.  Norm gains are ones (the seeded weights'); the
published code computes in bfloat16 where this is float32; the
multi-token-prediction module (``num_nextn_predict_layers``) is not
part of the served model and is not here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import common

ARCHITECTURE = "DeepseekV3ForCausalLM"
KEYS_PER_LAYER = 13
ROUTER_BIAS_STD = 0.01
# rope_scaling's numbers under flat keys: ``common.jitted_layer`` keeps
# a configuration's numbers and drops its nested groups
_YARN = ("factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
         "original_max_position_embeddings")


def flat(cfg: dict) -> dict:
    """``cfg`` with ``rope_scaling``'s numbers as ``yarn.<key>``."""
    out = dict(cfg)
    scaling = cfg.get("rope_scaling")
    if scaling:
        kind = scaling.get("rope_type", scaling.get("type"))
        if kind != "yarn":
            raise ValueError(f"rope_scaling of type {kind!r}")
        defaults = {"beta_fast": 32, "beta_slow": 1, "mscale": 1,
                    "mscale_all_dim": 0}
        for key in _YARN:
            out["yarn." + key] = float(scaling.get(key, defaults.get(key)))
    return out


def _sizes(cfg: dict) -> dict:
    held, ep = cfg["n_routed_experts"], cfg.get("expert_parallel_size", 1)
    return {"routed": held * ep,
            "lo": cfg.get("expert_parallel_rank", 0) * held}


def make_weights(cfg: dict, seed: int, bits: int = 16) -> dict:
    """The program's recipe (models/deepseek_v3.py ``init_params``): one
    root key from the seed split into ``13 · layers + 2``; layer ``i``
    takes keys ``13i ..``: wq_a, wq_b, wkv_a, wkv_b, wo, then the
    feed-forward's eight (dense: gate, up, down, five unused; routed:
    the shared expert's gate, up, down, router, router bias, w1, w3, w2
    with the held experts stacked on the leading axis); then the
    embedding and the head.  Every matrix ``normal × initializer_range``
    rounded to ``torch_dtype``; the bias ``normal × 0.01`` in float32.
    ``bits=4`` stores the matrices the program may quantize (not the
    router, ``wkv_b`` or the embedding) as the int4 control."""
    n_layers, h, nh = (cfg["num_hidden_layers"], cfg["hidden_size"],
                       cfg["num_attention_heads"])
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eff, v = cfg["moe_intermediate_size"], cfg["vocab_size"]
    shared = eff * cfg.get("n_shared_experts", 0)
    std, dt = cfg["initializer_range"], cfg["torch_dtype"]
    held, routed = cfg["n_routed_experts"], _sizes(cfg)["routed"]
    plain = common.normal_stored

    def store(key, shape):
        if bits == 16:
            return plain(key, shape, std, dt)
        return common.STORE[bits](key, shape, std, dt)

    def ffn_weights(k, width):
        return {"gate": store(k[0], (h, width)), "up": store(k[1], (h, width)),
                "down": store(k[2], (width, h))}

    keys = jax.random.split(common.root_key(seed),
                            KEYS_PER_LAYER * n_layers + 2)
    layers = []
    for i in range(n_layers):
        k = keys[KEYS_PER_LAYER * i:]
        layer = {"attn": {
            "wq_a": store(k[0], (h, ql)),
            "wq_b": store(k[1], (ql, nh * (nope + rope))),
            "wkv_a": store(k[2], (h, kvl + rope)),
            "wkv_b": plain(k[3], (kvl, nh * (nope + vd)), std, dt),
            "wo": store(k[4], (nh * vd, h)),
        }}
        if i < cfg["first_k_dense_replace"]:
            layer["ffn"] = ffn_weights(k[5:], cfg["intermediate_size"])
        else:
            if shared:
                layer["shared"] = ffn_weights(k[5:], shared)
            layer["router"] = plain(k[8], (h, routed), std, dt)
            layer["router_bias"] = plain(
                k[9], (routed,), ROUTER_BIAS_STD, "float32")
            layer["w1"] = store(k[10], (held, h, eff))
            layer["w3"] = store(k[11], (held, h, eff))
            layer["w2"] = store(k[12], (held, eff, h))
        layers.append(layer)
    n = KEYS_PER_LAYER * n_layers
    return {
        "embed": plain(keys[n], (v, h), std, dt),
        "lm_head": store(keys[n + 1], (h, v)),
        "layers": layers,
    }


# ---- YaRN ------------------------------------------------------------------
def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def inv_freq(cfg: dict) -> jax.Array:
    """[d / 2] inverse frequencies of the rotary lanes, ``cfg`` flat."""
    d, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    pair = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * pair / d)
    if "yarn.factor" not in cfg:
        return f
    orig = cfg["yarn.original_max_position_embeddings"]

    def pair_of(turns):
        return d * math.log(orig / (turns * 2.0 * math.pi)) / (
            2.0 * math.log(theta))

    lo = max(math.floor(pair_of(cfg["yarn.beta_fast"])), 0)
    hi = min(math.ceil(pair_of(cfg["yarn.beta_slow"])), d - 1)
    m = 1.0 - jnp.clip((pair - lo) / max(hi - lo, 0.001), 0.0, 1.0)
    return (f / cfg["yarn.factor"]) * (1.0 - m) + f * m


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if "yarn.factor" in cfg:
        scale *= mscale(cfg["yarn.factor"], cfg["yarn.mscale_all_dim"]) ** 2
    return scale


def rope_pairs(x: jax.Array, cfg: dict) -> jax.Array:
    """Rotary embedding over interleaved pairs, positions 0..T-1.
    x: [T, heads, d]: lanes (2i, 2i+1) rotate by ``pos · inv_freq_i``."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq(cfg)[None, :]
    factor = 1.0
    if "yarn.factor" in cfg:
        factor = mscale(cfg["yarn.factor"], cfg["yarn.mscale"]) / mscale(
            cfg["yarn.factor"], cfg["yarn.mscale_all_dim"])
    cos = jnp.cos(ang)[:, None, :] * factor
    sin = jnp.sin(ang)[:, None, :] * factor
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


# ---- the layer -------------------------------------------------------------
def mla(h: jax.Array, w: dict, cfg: dict) -> jax.Array:
    """One MLA sublayer, materialised.  h: [T, hidden] (normed)."""
    t = h.shape[0]
    nh, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kvl = cfg["kv_lora_rank"]
    deq = common.deq
    q = common.rms_norm(h @ deq(w["wq_a"]), eps) @ deq(w["wq_b"])
    q = q.reshape(t, nh, nope + rope)
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], cfg)], -1)
    ckr = h @ deq(w["wkv_a"])
    c = common.rms_norm(ckr[:, :kvl], eps)
    k_rope = rope_pairs(ckr[:, None, kvl:], cfg)
    kv = (c @ deq(w["wkv_b"])).reshape(t, nh, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (t, nh, rope))], -1)
    v = kv[..., nope:]
    scale = softmax_scale(cfg)
    outs = []
    for lo in range(0, t, common.QUERY_BLOCK):
        hi = min(lo + common.QUERY_BLOCK, t)
        s = jnp.einsum("qhd,thd->hqt", q[lo:hi], k) * scale
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqt,thd->qhd", p, v))
    return jnp.concatenate(outs, 0).reshape(t, nh * vd) @ deq(w["wo"])


def ffn(h, w: dict):
    deq = common.deq
    return (jax.nn.silu(h @ deq(w["gate"])) * (h @ deq(w["up"]))) @ deq(
        w["down"])


def route(u, w: dict, cfg: dict):
    """(indices [T, k] over every routed expert of the deployment,
    weights [T, k]): groups first, then experts; the bias moves the
    choice and never a weight."""
    t, groups = u.shape[0], cfg["n_group"]
    s = jax.nn.sigmoid(u @ common.deq(w["router"]))
    chosen_by = (s + w["router_bias"]).reshape(t, groups, -1)
    group_score = jnp.sort(chosen_by, axis=-1)[..., -2:].sum(axis=-1)
    _, kept = jax.lax.top_k(group_score, cfg["topk_group"])
    keep = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    masked = jnp.where(keep[:, :, None], chosen_by, 0.0).reshape(t, -1)
    _, idx = jax.lax.top_k(masked, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return idx, weights * cfg["routed_scaling_factor"]


def moe(u, w: dict, cfg: dict):
    """This chip's part of ``R(u)``: its own experts' products; picks
    of experts held elsewhere add nothing.  Every held expert runs on
    every position, one at a time, in the order held (a scan over the
    stacked experts compiles one expert, not sixteen)."""
    sz = _sizes(cfg)
    idx, weights = route(u, w, cfg)
    combine = jnp.zeros((u.shape[0], sz["routed"]), jnp.float32).at[
        jnp.arange(u.shape[0])[:, None], idx].add(weights)
    held = combine[:, sz["lo"]: sz["lo"] + cfg["n_routed_experts"]]

    def one(out, expert):
        w1, w3, w2, col = expert
        w1, w3, w2 = common.deq(w1), common.deq(w3), common.deq(w2)
        y = (jax.nn.silu(u @ w1) * (u @ w3)) @ w2
        return out + col[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u), (w["w1"], w["w3"], w["w2"], held.T))
    return out


def feed_forward(u, w: dict, cfg: dict):
    """``F_l(u)``: the dense FFN of a leading layer (its weights say
    which it is), else the shared expert whole plus this chip's part of
    the routed experts."""
    if "ffn" in w:
        return ffn(u, w["ffn"])
    out = moe(u, w, cfg)
    if "shared" in w:
        out = out + ffn(u, w["shared"])
    return out


def _layer(x, w, cfg: dict):
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = x + mla(common.rms_norm(x, eps), w["attn"], cfg)
        return h + feed_forward(common.rms_norm(h, eps), w, cfg)


def logprobs(weights: dict, cfg: dict, tokens, positions):
    cfg = flat(cfg)
    return common.run_layers(
        common.jitted_layer(_layer, cfg), weights, cfg, tokens, positions)
