"""Plain reference for ``LongcatFlashForCausalLM`` (LongCat-Flash-Chat),
in the **materialised** form: every head's key and value are
up-projected from the latent and attention is ordinary causal softmax
attention over them.  (The program computes the absorbed form over its
latent cache: different arithmetic for the same function.)

One layer, with ``n`` RMS norms, ``A`` an MLA sublayer, ``F`` a dense
FFN, ``M`` the routed layer:

    h = x + A0(n0(x));  u = n1(h);  m = M(u);  h = h + F0(u)
    h = h + A1(n2(h));  y = h + F1(n3(h)) + m

``A``: ``q = W_qb · norm(W_qa · x)`` per head ``[q_nope | q_rope]``,
both × (hidden / q_lora_rank)^½; ``[c | k_rope] = W_kva · x``, ``c =
norm(c)`` × (hidden / kv_lora_rank)^½, ``k_rope`` one unscaled vector
for all heads; RoPE on ``q_rope`` and ``k_rope`` by interleaved pairs
(lanes 2i, 2i+1); per head ``[k_nope | v] = W_kvb · c``; scores
``(q_nope·k_nope + q_rope·k_rope)`` × (nope + rope)^-½, causal softmax,
times ``v``, heads concatenated, ``W_o``.

``M``: float32 ``s = softmax(W_r · u)`` over ``n_routed_experts ×
expert_parallel_size + zero_expert_num`` outputs; the ``moe_topk``
indices are the top of ``s + b``; weights ``s`` at those indices × the
scaling factor, not renormalised.  **The share**: this chip holds
``n_routed_experts`` experts, those of rank ``expert_parallel_rank``
among ``expert_parallel_size`` chips; a pick of one of them adds its
product, a pick of an identity expert adds ``u``, a pick of an expert
held elsewhere adds nothing — in the program and here alike.

Departures from the published code (`transformers`
``modeling_longcat_flash``): none in the function computed.  Its
``apply_rotary_pos_emb_interleave`` moves each pair's lanes to (i, i +
d/2) before rotating, on the query and the key alike, which leaves every
score as it is here; norm gains are ones (the seeded weights'); the
published code computes in bfloat16 where this is float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common

ARCHITECTURE = "LongcatFlashForCausalLM"
KEYS_PER_LAYER = 21


def _sizes(cfg: dict) -> dict:
    held, ep = cfg["n_routed_experts"], cfg.get("expert_parallel_size", 1)
    return {
        "routed": held * ep,
        "width": held * ep + cfg["zero_expert_num"],
        "lo": cfg.get("expert_parallel_rank", 0) * held,
    }


def make_weights(cfg: dict, seed: int, bits: int = 16) -> dict:
    """The program's recipe (models/longcat_flash.py ``init_params``):
    one root key from the seed split into ``21 · layers + 2``; layer
    ``i`` takes keys ``21i ..``: sublayers 0 and 1 (wq_a, wq_b, wkv_a,
    wkv_b, wo), dense FFNs 0 and 1 (gate, up, down), router, router
    bias, w1, w3, w2 (experts stacked on the leading axis); then the
    embedding and the head.  Every matrix ``normal × initializer_range``
    rounded to ``torch_dtype``; the bias ``normal × (1 / router width)``
    in float32.  ``bits=4`` stores the matrices the program may quantize
    (not the router, ``wkv_b`` or the embedding) as the int4 control."""
    n_layers, h, nh = cfg["num_layers"], cfg["hidden_size"], cfg[
        "num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    ffn, eff, v = (cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"],
                   cfg["vocab_size"])
    std, dt = cfg["initializer_range"], cfg["torch_dtype"]
    held, width = cfg["n_routed_experts"], _sizes(cfg)["width"]
    plain = common.normal_stored

    def store(key, shape):
        if bits == 16:
            return plain(key, shape, std, dt)
        return common.STORE[bits](key, shape, std, dt)

    keys = jax.random.split(common.root_key(seed),
                            KEYS_PER_LAYER * n_layers + 2)
    layers = []
    for i in range(n_layers):
        lk = keys[KEYS_PER_LAYER * i:]
        layer = {"attn": [], "ffn": []}
        for j in range(2):
            k = lk[5 * j:]
            layer["attn"].append({
                "wq_a": store(k[0], (h, ql)),
                "wq_b": store(k[1], (ql, nh * (nope + rope))),
                "wkv_a": store(k[2], (h, kvl + rope)),
                "wkv_b": plain(k[3], (kvl, nh * (nope + vd)), std, dt),
                "wo": store(k[4], (nh * vd, h)),
            })
        for j in range(2):
            k = lk[10 + 3 * j:]
            layer["ffn"].append({
                "gate": store(k[0], (h, ffn)),
                "up": store(k[1], (h, ffn)),
                "down": store(k[2], (ffn, h)),
            })
        layer["router"] = plain(lk[16], (h, width), std, dt)
        layer["router_bias"] = plain(lk[17], (width,), 1.0 / width, "float32")
        layer["w1"] = store(lk[18], (held, h, eff))
        layer["w3"] = store(lk[19], (held, h, eff))
        layer["w2"] = store(lk[20], (held, eff, h))
        layers.append(layer)
    n = KEYS_PER_LAYER * n_layers
    return {
        "embed": plain(keys[n], (v, h), std, dt),
        "lm_head": store(keys[n + 1], (h, v)),
        "layers": layers,
    }


def rope_pairs(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over interleaved pairs, positions 0..T-1.
    x: [T, heads, d]: lanes (2i, 2i+1) rotate by ``pos · theta^(-2i/d)``."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def mla(h: jax.Array, w: dict, cfg: dict) -> jax.Array:
    """One MLA sublayer, materialised.  h: [T, hidden] (normed)."""
    t = h.shape[0]
    nh, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kvl, hidden = cfg["kv_lora_rank"], cfg["hidden_size"]
    deq = common.deq
    q = common.rms_norm(h @ deq(w["wq_a"]), eps) @ deq(w["wq_b"])
    q = q.reshape(t, nh, nope + rope) * (hidden / cfg["q_lora_rank"]) ** 0.5
    q = jnp.concatenate(
        [q[..., :nope], rope_pairs(q[..., nope:], cfg["rope_theta"])], -1)
    ckr = h @ deq(w["wkv_a"])
    c = common.rms_norm(ckr[:, :kvl], eps) * (hidden / kvl) ** 0.5
    k_rope = rope_pairs(ckr[:, None, kvl:], cfg["rope_theta"])
    kv = (c @ deq(w["wkv_b"])).reshape(t, nh, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (t, nh, rope))], -1)
    v = kv[..., nope:]
    outs = []
    for lo in range(0, t, common.QUERY_BLOCK):
        hi = min(lo + common.QUERY_BLOCK, t)
        s = jnp.einsum("qhd,thd->hqt", q[lo:hi], k) * (nope + rope) ** -0.5
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqt,thd->qhd", p, v))
    return jnp.concatenate(outs, 0).reshape(t, nh * vd) @ deq(w["wo"])


def ffn(h, w: dict):
    deq = common.deq
    return (jax.nn.silu(h @ deq(w["gate"])) * (h @ deq(w["up"]))) @ deq(
        w["down"])


def route(u, w: dict, cfg: dict):
    """(indices [T, k], weights [T, k]): the bias moves the choice and
    never a weight; weights are not renormalised."""
    scores = jax.nn.softmax(u @ common.deq(w["router"]), axis=-1)
    _, idx = jax.lax.top_k(scores + w["router_bias"], cfg["moe_topk"])
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, weights * cfg["routed_scaling_factor"]


def moe(u, w: dict, cfg: dict):
    """This chip's part of the routed layer: its own experts' products
    and the identity experts' part; picks of experts held elsewhere add
    nothing.  Every held expert runs on every position, one at a time,
    in the order held (a scan over the stacked experts: sixteen unrolled
    experts were three quarters of this layer's 95 s of compiling at
    1,024 positions, for a described v5e)."""
    sz = _sizes(cfg)
    idx, weights = route(u, w, cfg)
    combine = jnp.zeros((u.shape[0], sz["width"]), jnp.float32).at[
        jnp.arange(u.shape[0])[:, None], idx].add(weights)
    out = combine[:, sz["routed"]:].sum(axis=-1, keepdims=True) * u
    held = combine[:, sz["lo"]: sz["lo"] + cfg["n_routed_experts"]]

    def one(out, expert):
        w1, w3, w2, col = expert
        w1, w3, w2 = common.deq(w1), common.deq(w3), common.deq(w2)
        y = (jax.nn.silu(u @ w1) * (u @ w3)) @ w2
        return out + col[:, None] * y, None

    out, _ = jax.lax.scan(one, out, (w["w1"], w["w3"], w["w2"], held.T))
    return out


def _layer(x, w, cfg: dict):
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = x + mla(common.rms_norm(x, eps), w["attn"][0], cfg)
        u = common.rms_norm(h, eps)
        m = moe(u, w, cfg)
        h = h + ffn(u, w["ffn"][0])
        h = h + mla(common.rms_norm(h, eps), w["attn"][1], cfg)
        return h + ffn(common.rms_norm(h, eps), w["ffn"][1]) + m


def logprobs(weights: dict, cfg: dict, tokens, positions):
    return common.run_layers(
        common.jitted_layer(_layer, cfg), weights, cfg, tokens, positions)
