"""Plain reference for ``FalconH1ForCausalLM`` (tiiuae
Falcon-H1-34B-Instruct): one whole sequence, float32, the state-space
recurrence **token by token** (a ``lax.scan`` over positions: no
chunks, no kernel, no stored state), full causal attention, no cache.
(The program computes a flat batch of ragged chunks, walks the
recurrence in tiles of 128 tokens or one token a micro-step, and keeps
a convolution state and a state-space state a sequence between steps:
different arithmetic for the same function.)

With ``e`` the embedding row, per layer (all norms RMS, unit gains)::

    x_0 = e · embedding_multiplier
    u   = norm(x)
    x   = x + Mixer(u · ssm_in_multiplier) · ssm_out_multiplier
            + Attn(u · attention_in_multiplier) · attention_out_multiplier
    v   = norm(x)
    x   = x + W_down(W_up v ⊙ silu(W_gate v · mlp_multipliers[0]))
              · mlp_multipliers[1]
    logits = W_head · norm(x_L) · lm_head_multiplier        (head untied)

``Attn``: ``q, k, v`` without bias; ``k ← k · key_multiplier``; RoPE by
half-rotation at ``rope_theta``, no scaling; causal softmax of ``q·k ·
head_dim^-½``, ``num_attention_heads / num_key_value_heads`` query heads
a KV head; ``W_o``.

``Mixer`` (``d_ssm`` = ``mamba_d_ssm``, ``H`` = ``mamba_n_heads`` heads
of ``P`` = ``mamba_d_head``, ``G`` = ``mamba_n_groups`` groups of ``N`` =
``mamba_d_state``, ``mamba_d_conv`` taps)::

    z | x | B | C | dt = (W_in · u') ⊙ ssm_multipliers[0..4], by segment
    xBC_t  = silu(Σ_j w_j ⊙ xBC_{t-3+j} + b)      depthwise, causal, 0 before 0
    Δ_t    = softplus(dt_t + dt_bias)              (no clamp: (0, inf))
    S_t[h] = exp(Δ_t[h]·A[h]) · S_{t-1}[h] + Δ_t[h] · x_t[h] ⊗ B_t[g(h)]
    y_t[h] = S_t[h] · C_t[g(h)] + D[h] · x_t[h]        A = −exp(A_log), S_{-1} = 0
    y      = y ⊙ silu(z); then RMS norm over each group's d_ssm / G lanes
    out    = W_out · y

This is the installed ``transformers`` ``modeling_falcon_h1.py``
(``FalconH1Mixer.torch_forward``, ``FalconH1RMSNormGated`` with
``norm_before_gate`` false, ``compute_mup_vector``,
``FalconH1DecoderLayer``, ``FalconH1ForCausalLM.forward``) read line by
line; nothing is imported from it.  Departures, none in the function
computed: norm gains are ones (the seeded weights'); the published
convolution is a ``Conv1d`` with ``groups = channels`` and padding 3 cut
back to the sequence's length, which is the sum over four shifted
copies here; the published naive path walks the recurrence in chunks
where this walks it a token at a time; it computes in bfloat16 where
this is float32.

It has to fit the chip beside nothing else at 10.5 GB of weights: they
stay in their stored bfloat16 and are widened inside a layer's program;
the head is applied in blocks of vocabulary columns and only at the
scored positions.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common

ARCHITECTURE = "FalconH1ForCausalLM"
# The seeded recipe, as the program's models/falcon_h1.py states it: a
# matrix [in, out] is ``normal × gain · in^-½ / m``, ``m`` the
# multipliers between its input and its output.
QK_GAIN = 1.5
WO_GAIN = 2.0
DT_MIN, DT_MAX = 0.001, 0.1
A_MIN, A_MAX = 1.0, 16.0
CONV_BIAS_STD = 0.02
KEYS_PER_LAYER = 17
SEGMENTS = ("in_z", "in_x", "in_b", "in_c", "in_dt")
HEAD_BLOCKS = 8  # the head's columns, a block at a time
INT4_GROUP = common.INT4_GROUP


def d_ssm(cfg: dict) -> int:
    if cfg.get("mamba_d_ssm") is not None:
        return cfg["mamba_d_ssm"]
    return cfg["mamba_expand"] * cfg["hidden_size"]


def segment_widths(cfg: dict) -> tuple:
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return (d_ssm(cfg), d_ssm(cfg), gn, gn, cfg["mamba_n_heads"])


def _inv_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


@functools.partial(jax.jit, static_argnames=("group",))
def _int4(w, group: int):
    """A stored tensor [in, cols] as symmetric int4, one scale per
    ``group`` input rows and output channel (``common.normal_int4``'s
    storage, made from the tensor and not from its key, so that a large
    one can be made a block of columns at a time)."""
    rows, cols = w.shape
    grouped = w.astype(jnp.float32).reshape(rows // group, group, cols)
    s = jnp.maximum(jnp.max(jnp.abs(grouped), axis=1) / 7.0, 1e-8)
    q = jnp.clip(jnp.round(grouped / s[:, None, :]), -8, 7)
    return q.astype(jnp.int8).reshape(rows, cols), s


def _int4_by_columns(w, blocks: int):
    group = min(INT4_GROUP, w.shape[0])
    step = -(-w.shape[1] // blocks)
    parts = [_int4(w[:, lo: lo + step], group)
             for lo in range(0, w.shape[1], step)]
    return (jnp.concatenate([q for q, _ in parts], axis=1),
            jnp.concatenate([s for _, s in parts], axis=1))


def make_weights(cfg: dict, seed: int, bits: int = 16) -> dict:
    """The program's recipe (models/falcon_h1.py ``init_params``): one
    root key from the seed split into ``17 · layers + 2``; layer ``i``
    takes keys ``17i ..``: wq, wk, wv, wo; in_proj's five segments (z,
    x, B, C, dt), kept apart here; the taps (``normal × taps^-½``) and
    their bias (``normal × 0.02``); ``dt_bias`` (the inverse softplus of
    ``exp(uniform(ln 0.001, ln 0.1))``); ``A_log`` (``ln uniform(1,
    16)``); out_proj; gate, up, down.  Then the embedding (``normal /
    embedding_multiplier``) and the head.  A matrix [in, out] is
    ``normal × gain · in^-½ / m`` rounded to ``torch_dtype``, ``m`` the
    multipliers on its path (q, v: attention_in; k: attention_in · key;
    o: attention_out; an in_proj segment: ssm_in · its ssm_multiplier;
    out_proj: ssm_out; gate, down: the mlp's two; the head: lm_head),
    ``gain`` 1.5 for q and k, 2 for o; ``D`` is ones.  ``bits=4`` stores
    the matrices the program may quantize (not the embedding, the taps,
    their bias, ``dt_bias``, ``A_log``, ``D``) as the int4 control."""
    h, nh, nkv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    im, v, hs = cfg["intermediate_size"], cfg["vocab_size"], cfg[
        "mamba_n_heads"]
    taps, dt = cfg["mamba_d_conv"], cfg["torch_dtype"]
    conv_dim = d_ssm(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    a_in = cfg["attention_in_multiplier"]
    g0, g1 = cfg["mlp_multipliers"]
    n_layers = cfg["num_hidden_layers"]
    plain = common.normal_stored

    def mat(key, shape, mult, gain=1.0):
        w = plain(key, shape, gain * shape[0] ** -0.5 / mult, dt)
        if bits == 16:
            return w
        assert bits == 4, bits
        return _int4_by_columns(w, HEAD_BLOCKS if shape[1] >= 2**16 else 1)

    def uniform(key, lo, hi):
        return jax.random.uniform(
            key, (hs,), jnp.float32, minval=lo, maxval=hi)

    keys = jax.random.split(
        common.root_key(seed), KEYS_PER_LAYER * n_layers + 2)
    layers = []
    for i in range(n_layers):
        k = keys[KEYS_PER_LAYER * i:]
        layer = {
            "wq": mat(k[0], (h, nh * d), a_in, QK_GAIN),
            "wk": mat(k[1], (h, nkv * d), a_in * cfg["key_multiplier"],
                      QK_GAIN),
            "wv": mat(k[2], (h, nkv * d), a_in),
            "wo": mat(k[3], (nh * d, h), cfg["attention_out_multiplier"],
                      WO_GAIN),
            "conv_w": plain(k[9], (taps, conv_dim), taps**-0.5, dt),
            "conv_b": plain(k[10], (conv_dim,), CONV_BIAS_STD, dt),
            "dt_bias": _inv_softplus(jnp.exp(uniform(
                k[11], math.log(DT_MIN), math.log(DT_MAX)))),
            "a_log": jnp.log(uniform(k[12], A_MIN, A_MAX)),
            "d_skip": jnp.ones((hs,), jnp.float32),
            "out_proj": mat(k[13], (d_ssm(cfg), h),
                            cfg["ssm_out_multiplier"]),
            "gate": mat(k[14], (h, im), g0),
            "up": mat(k[15], (h, im), 1.0),
            "down": mat(k[16], (im, h), g1),
        }
        for seg, (name, width, m) in enumerate(zip(
                SEGMENTS, segment_widths(cfg), cfg["ssm_multipliers"])):
            layer[name] = mat(
                k[4 + seg], (h, width), cfg["ssm_in_multiplier"] * m)
        layers.append(layer)
    return {
        "embed": plain(keys[-2], (v, h),
                       1.0 / cfg["embedding_multiplier"], dt),
        "lm_head": mat(keys[-1], (h, v), cfg["lm_head_multiplier"]),
        "layers": layers,
    }


# ---- one layer over one whole sequence ----------------------------------
def attention(u: jax.Array, w: dict, cfg: dict, stats: dict) -> jax.Array:
    t = u.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    deq = common.deq
    ua = u * cfg["attention_in_multiplier"]
    q = (ua @ deq(w["wq"])).reshape(t, nh, d)
    k = (ua @ deq(w["wk"])).reshape(t, nkv, d) * cfg["key_multiplier"]
    v = (ua @ deq(w["wv"])).reshape(t, nkv, d)
    q = common.rope(q, float(cfg["rope_theta"])).reshape(t, nkv, nh // nkv, d)
    k = common.rope(k, float(cfg["rope_theta"]))  # 1e11: no int32
    outs, second = [], 0.0
    for lo in range(0, t, common.QUERY_BLOCK):
        hi = min(lo + common.QUERY_BLOCK, t)
        s = jnp.einsum("qkgd,tkd->kgqt", q[lo:hi], k) * (d**-0.5)
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(t)[None, :]
        second = second + jnp.sum(jnp.where(mask, s * s, 0.0))
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p, v))
    # the scores' spread before the softmax, over the causal pairs
    # (their mean is ~0: q and k are independent draws)
    stats["score_std"] = jnp.sqrt(second / (nh * t * (t + 1) / 2))
    return jnp.concatenate(outs, 0).reshape(t, nh * d) @ deq(w["wo"])


def mixer(u: jax.Array, w: dict, cfg: dict, stats: dict) -> jax.Array:
    t = u.shape[0]
    deq = common.deq
    hs, hp, g, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                    cfg["mamba_n_groups"], cfg["mamba_d_state"])
    taps = cfg["mamba_d_conv"]
    up = u * cfg["ssm_in_multiplier"]
    z, x, b, c, dt = [
        (up @ deq(w[name])) * m
        for name, m in zip(SEGMENTS, cfg["ssm_multipliers"])]
    xbc = jnp.pad(jnp.concatenate([x, b, c], axis=-1),
                  ((taps - 1, 0), (0, 0)))  # zero rows before position 0
    tap_w = deq(w["conv_w"])
    conv = sum(tap_w[j] * xbc[j: j + t] for j in range(taps))
    xbc = jax.nn.silu(conv + deq(w["conv_b"]))
    x = xbc[:, : hs * hp].reshape(t, hs, hp)
    b = xbc[:, hs * hp: hs * hp + g * n].reshape(t, g, n)
    c = xbc[:, hs * hp + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [T, H]
    a = -jnp.exp(w["a_log"])
    decay = jnp.exp(dt * a)  # [T, H]
    stats["decay_lo"] = jnp.min(jnp.median(decay, axis=0))
    stats["decay_hi"] = jnp.max(jnp.median(decay, axis=0))

    def step(state, inp):
        x_t, dt_t, decay_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, hs // g, axis=0)  # [H, N]
        c_h = jnp.repeat(c_t, hs // g, axis=0)
        state = (decay_t[:, None, None] * state
                 + (dt_t[:, None] * x_t)[..., None] * b_h[:, None, :])
        y_t = (state * c_h[:, None, :]).sum(-1) + w["d_skip"][:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(
        step, jnp.zeros((hs, hp, n), jnp.float32), (x, dt, decay, b, c))
    y = y.reshape(t, hs * hp) * jax.nn.silu(z)  # the gate first,
    y = y.reshape(t, g, hs * hp // g)  # then the norm a group
    y = y * jax.lax.rsqrt(
        jnp.mean(y * y, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return y.reshape(t, hs * hp) @ deq(w["out_proj"])


def layer_parts(x, w: dict, cfg: dict):
    """(the layer's output, what the recipe is held to: each branch's
    RMS into the residual, the scores' spread, the per-token decay's
    range over the heads)."""
    stats = {}
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        g0, g1 = cfg["mlp_multipliers"]
        u = common.rms_norm(x, eps)
        mixed = mixer(u, w, cfg, stats) * cfg["ssm_out_multiplier"]
        attn = attention(u, w, cfg, stats) * cfg["attention_out_multiplier"]
        x = x + mixed + attn
        v = common.rms_norm(x, eps)
        deq = common.deq
        mlp = ((v @ deq(w["up"])) * jax.nn.silu((v @ deq(w["gate"])) * g0)
               ) @ deq(w["down"]) * g1

        def rms(y):
            return jnp.sqrt(jnp.mean(y * y))

        stats.update(mixer_rms=rms(mixed), attn_rms=rms(attn),
                     mlp_rms=rms(mlp))
        return x + mlp, stats


@functools.lru_cache(maxsize=8)
def _jitted(frozen: str, probe: bool):
    cfg = json.loads(frozen)
    if probe:
        return jax.jit(lambda x, w: layer_parts(x, w, cfg))
    return jax.jit(lambda x, w: layer_parts(x, w, cfg)[0])


def _layer_fn(cfg: dict, probe: bool = False):
    """One layer jitted once per configuration (its numbers and its
    lists of multipliers are what a layer reads)."""
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, list)) or v is None}
    return _jitted(json.dumps(keep, sort_keys=True), probe)


@functools.partial(jax.jit, static_argnames=("mult",))
def _head_block(sel, block, mult: float):
    with jax.default_matmul_precision("highest"):
        return (sel @ common.deq(block)) * mult


def _columns(w, lo: int, hi: int):
    if isinstance(w, tuple):
        return w[0][:, lo:hi], w[1][:, lo:hi]
    return w[:, lo:hi]


def _logits(weights: dict, cfg: dict, x, positions) -> jax.Array:
    """Final norm at the asked positions only, then the head a block of
    vocabulary columns at a time: [len(positions), vocab]."""
    sel = common.rms_norm(x, cfg["rms_norm_eps"])[positions]
    v = cfg["vocab_size"]
    step = -(-v // HEAD_BLOCKS)
    return jnp.concatenate([
        _head_block(sel, _columns(weights["lm_head"], lo, min(lo + step, v)),
                    cfg["lm_head_multiplier"])
        for lo in range(0, v, step)], axis=-1)


def _embed(weights: dict, cfg: dict, tokens, t_pad: int) -> jax.Array:
    ids = np.zeros(t_pad, np.int32)
    ids[: len(tokens)] = tokens
    return common.embed(weights, jnp.asarray(ids)) * cfg[
        "embedding_multiplier"]


def logprobs(weights: dict, cfg: dict, tokens, positions):
    """Log-softmax rows [len(positions), vocab] as a numpy array.  The
    sequence is padded behind (``common.pad_len``): everything here is
    causal, so what lies behind a position never reaches it."""
    x = _embed(weights, cfg, tokens, common.pad_len(len(tokens)))
    layer = _layer_fn(cfg)
    for lw in weights["layers"]:
        x = layer(x, lw)
    pos = np.zeros(common.pad_len(len(positions)), np.int32)
    pos[: len(positions)] = positions
    out = jax.nn.log_softmax(
        _logits(weights, cfg, x, jnp.asarray(pos)), axis=-1)
    return np.asarray(out)[: len(positions)]


def spreads(weights: dict, cfg: dict, tokens) -> dict:
    """What the seeded recipe is held to, over one unpadded sequence:
    per layer the scores' spread before the softmax, the three
    branches' RMS into the residual and the range over the heads of the
    median per-token decay ``exp(ΔA)``; the logits' spread at every
    position."""
    x = _embed(weights, cfg, tokens, len(tokens))
    layer = _layer_fn(cfg, probe=True)
    rows = []
    for lw in weights["layers"]:
        x, stats = layer(x, lw)
        rows.append({k: float(v) for k, v in stats.items()})
    logits = _logits(weights, cfg, x, jnp.arange(len(tokens)))
    return {"layers": rows, "logits_std": float(jnp.std(logits))}
