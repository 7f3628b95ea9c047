"""Shared pieces of the plain references: seeded weights, int8
storage, RMS norm, rotary embedding, causal attention.

Everything here is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``.  Nothing is imported from
the program: the weights are made from the seed by the recipe the
configuration file states (``weights`` there), the forward pass is the
published one.  No kernel, no cache, no batching: one sequence, every
position attends over the whole prefix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512  # attention is computed a block of queries at a time
MIN_PAD = 128


def pad_len(n: int) -> int:
    """Sequences are padded to a power of two, so that a checkout
    compiles half a dozen shapes of the forward pass (128 to 4,096), not
    one per length: each takes tens of seconds to compile at the cell's
    widths, and a shape first met in a later run is that much more
    ``check_s`` in a run that has 360 s in all.  (Multiples of 512 above
    512 made ten shapes, and a fresh cache then cost one run 163 s.)"""
    p = MIN_PAD
    while p < n:
        p *= 2
    return p


# ---- weights from the seed ---------------------------------------------
def root_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed)


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def normal_stored(key, shape: tuple, std: float, dtype: str) -> jax.Array:
    """``normal * std`` rounded to the configuration's ``torch_dtype``
    (bfloat16 in every benchmark configuration): the stored form of
    every unquantized tensor (embedding, router)."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def normal_int8(key, shape: tuple, std: float, dtype: str):
    """A tensor as above, stored as the configuration states it:
    symmetric int8, one scale per output channel (``s = max|w| / 127``
    over the input dim, ``q = round(w / s)``).  One operation at a
    time, not one fused program: a compiler may turn a fused ``w / s``
    into ``w * (1 / s)``, which rounds a tie the other way."""
    w = normal_stored(key, shape, std, dtype).astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(w / s[..., None, :]), -127, 127).astype(jnp.int8)
    return q, s


INT4_GROUP = 128


def normal_int4(key, shape: tuple, std: float, dtype: str):
    """The lower-precision control's storage: the same seeded tensor as
    symmetric int4, one scale per ``INT4_GROUP`` input rows and output
    channel (``s = max|w| / 7`` over the group, ``q = round(w / s)`` in
    -8..7).  Kept one value a byte; only the values matter here."""
    w = normal_stored(key, shape, std, dtype).astype(jnp.float32)
    g = min(INT4_GROUP, shape[-2])
    grouped = w.reshape(*shape[:-2], shape[-2] // g, g, shape[-1])
    s = jnp.maximum(jnp.max(jnp.abs(grouped), axis=-2) / 7.0, 1e-8)
    q = jnp.clip(jnp.round(grouped / s[..., None, :]), -8, 7)
    return q.astype(jnp.int8).reshape(shape), s


STORE = {8: normal_int8, 4: normal_int4}


def deq(w) -> jax.Array:
    """Stored weight -> float32: ``(q, scale)`` pairs dequantize — a
    scale an output channel (int8: ``scale`` has one dim fewer than
    ``q``) or a scale a group of input rows (the int4 control: the same
    number of dims) — and unquantized tensors widen."""
    if isinstance(w, tuple):
        q, s = w
        if s.ndim == q.ndim:
            groups = s.shape[-2]
            grouped = q.astype(jnp.float32).reshape(
                *q.shape[:-2], groups, q.shape[-2] // groups, q.shape[-1])
            return (grouped * s[..., None, :]).reshape(q.shape)
        return q.astype(jnp.float32) * s[..., None, :]
    return w.astype(jnp.float32)


# ---- layers --------------------------------------------------------------
def rms_norm(x: jax.Array, eps: float) -> jax.Array:
    """RMS norm with unit gain (the seeded weights' gains are ones)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, rotate-half convention, positions 0..T-1.
    x: [T, heads, head_dim]."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h: jax.Array, w: dict, cfg: dict) -> jax.Array:
    """Grouped-query causal self-attention over the whole sequence.
    h: [T, hidden] (already normed).  Returns [T, hidden]."""
    t = h.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    q = rope((h @ deq(w["wq"])).reshape(t, nh, d), cfg["rope_theta"])
    k = rope((h @ deq(w["wk"])).reshape(t, nkv, d), cfg["rope_theta"])
    v = (h @ deq(w["wv"])).reshape(t, nkv, d)
    g = nh // nkv
    q = q.reshape(t, nkv, g, d)
    outs = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        s = jnp.einsum("qkgd,tkd->kgqt", q[lo:hi], k) * (d**-0.5)
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p, v))
    o = jnp.concatenate(outs, axis=0).reshape(t, nh * d)
    return o @ deq(w["wo"])


def embed(weights: dict, tokens: jax.Array) -> jax.Array:
    return weights["embed"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logprobs(x, positions, lm_head, eps: float) -> jax.Array:
    """Final norm, output head and log-softmax at the asked positions."""
    with jax.default_matmul_precision("highest"):
        sel = rms_norm(x, eps)[positions]
        return jax.nn.log_softmax(sel @ deq(lm_head), axis=-1)


def run_layers(layer_fn, weights: dict, cfg: dict, tokens, positions):
    """The whole forward pass of one sequence: log-softmax rows
    [len(positions), vocab] as a numpy array.  ``layer_fn(x, layer
    weights)`` is the architecture's block, jitted by the caller."""
    n = len(tokens)
    t_pad = pad_len(n)
    ids = np.zeros(t_pad, np.int32)
    ids[:n] = tokens
    pos = np.asarray(positions, np.int32)
    p_pad = pad_len(len(pos))
    pos_padded = np.zeros(p_pad, np.int32)
    pos_padded[: len(pos)] = pos
    x = embed(weights, jnp.asarray(ids))
    for lw in weights["layers"]:
        x = layer_fn(x, lw)
    out = head_logprobs(
        x, jnp.asarray(pos_padded), weights["lm_head"], cfg["rms_norm_eps"]
    )
    return np.asarray(out)[: len(pos)]


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted(
        (k, v) for k, v in cfg.items() if isinstance(v, (int, float))
    ))


@functools.lru_cache(maxsize=8)
def _jitted(layer, frozen: tuple):
    cfg = dict(frozen)
    return jax.jit(lambda x, w: layer(x, w, cfg))


def jitted_layer(layer, cfg: dict):
    """``layer(x, weights, cfg)`` jitted once per configuration (its
    numbers are what a layer reads)."""
    return _jitted(layer, _freeze(cfg))


def base_weights(cfg: dict, seed: int, per_layer: tuple, bits: int = 8) -> dict:
    """The tensors both architectures share, by the recipe in the
    configuration's ``weights``: one root key from the seed, split into
    ``7 * layers + 3`` keys; layer ``i`` takes keys ``7i..7i+6`` for
    wq, wk, wv, wo, gate, up, down, then one for the embedding and one
    for the output head.  ``per_layer`` names which of the seven are
    kept (the MoE replaces the dense MLP, and its keys go unused).
    ``bits=4`` stores the quantized tensors as the int4 control does."""
    n_layers = cfg["num_hidden_layers"]
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    im, v = cfg["intermediate_size"], cfg["vocab_size"]
    std, dt = cfg["initializer_range"], cfg["torch_dtype"]
    shapes = {
        "wq": (h, nh * d), "wk": (h, nkv * d), "wv": (h, nkv * d),
        "wo": (nh * d, h), "gate": (h, im), "up": (h, im), "down": (im, h),
    }
    order = ("wq", "wk", "wv", "wo", "gate", "up", "down")
    keys = jax.random.split(root_key(seed), 7 * n_layers + 3)
    layers = []
    for i in range(n_layers):
        layers.append({
            name: STORE[bits](keys[7 * i + j], shapes[name], std, dt)
            for j, name in enumerate(order) if name in per_layer
        })
    return {
        "embed": normal_stored(keys[7 * n_layers], (v, h), std, dt),
        "lm_head": STORE[bits](keys[7 * n_layers + 1], (h, v), std, dt),
        "layers": layers,
    }
