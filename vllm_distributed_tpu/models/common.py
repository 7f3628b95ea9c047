"""Shared functional building blocks for the model zoo.

Models here are *functions over pytrees*, not stateful modules: params are
nested dicts of jax arrays, the forward pass is pure, and everything
composes with jit/pjit/NamedSharding (SURVEY.md §7 design stance).  Linear
weights are stored input-major (``[in, out]``) so application is a plain
``x @ w`` that XLA tiles onto the MXU without transposes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# The parts of a layer and of a step, as ``jax.named_scope`` names them
# in every program the runner builds (llama.py, mixtral.py,
# worker/model_runner.py).  A scope travels with an operation as the
# tail of its ``op_name`` through jax.export and XLA into a profile;
# the benchmark's readers file device time under these names, so a
# rename here renames a metric's source.
SCOPE_EMBED = "embed"
SCOPE_ATTN_QKV = "attn_qkv"  # norm, q/k/v projections, rope
SCOPE_KV_WRITE = "kv_write"
SCOPE_ATTN = "attn"  # the attention kernel call and its layout glue
SCOPE_ATTN_OUT = "attn_out"  # o projection and residual
SCOPE_MOE_ROUTER = "moe_router"
SCOPE_MOE_EXPERTS = "moe_experts"
SCOPE_MLP = "mlp"  # post-attention norm; the dense MLP; MoE combine
SCOPE_LM_HEAD = "lm_head"  # final norm, row gather, logits
SCOPE_SAMPLER = "sampler"
# latent attention only: queries into the latent space (q_nope · W_uk)
# and the attended latent back out of it (· W_uv)
SCOPE_MLA_ABSORB = "mla_absorb"
# a gated short-convolution operator whole: its norm, both projections,
# the gates, the taps, the sequence state's read and write
SCOPE_SHORT_CONV = "short_conv"
# a routed layer's always-on shared expert (norm excluded): what every
# chip of an expert-parallel group computes alike
SCOPE_MOE_SHARED = "moe_shared"
# a Mamba-2 mixer whole (the layer's input norm, in_proj, the
# convolution and its state, the gate, the grouped norm, out_proj) and,
# inside it, the state-space recurrence alone (the chunked scan or the
# one-token update, with the state's read and write)
SCOPE_SSM_MIXER = "ssm_mixer"
SCOPE_SSM_SCAN = "ssm_scan"
SCOPES = (
    SCOPE_EMBED, SCOPE_ATTN_QKV, SCOPE_KV_WRITE, SCOPE_ATTN,
    SCOPE_ATTN_OUT, SCOPE_MOE_ROUTER, SCOPE_MOE_EXPERTS, SCOPE_MLP,
    SCOPE_LM_HEAD, SCOPE_SAMPLER, SCOPE_MLA_ABSORB, SCOPE_SHORT_CONV,
    SCOPE_MOE_SHARED, SCOPE_SSM_MIXER, SCOPE_SSM_SCAN,
)


class SupportsQuantization:
    """Weight-only quantization hooks shared by the model zoo.

    Subclasses set ``QUANT_PARAMS`` (leaf names of the big matmuls —
    embeddings/norms/biases/routers stay in the model dtype) and call
    ``_init_quant(model_config)`` from ``__init__``.  A model that skips
    both simply never quantizes (the loader checks ``quant_method``)."""

    QUANT_PARAMS: frozenset = frozenset()

    def _init_quant(self, model_config) -> None:
        self.quant_method = model_config.quantization

    def should_quantize(self, path: tuple) -> bool:
        """Whether the param at `path` gets weight-only quantization
        (per-expert paths end in an int index; the name precedes it)."""
        names = [k for k in path if isinstance(k, str)]
        return bool(names) and names[-1] in self.QUANT_PARAMS


class CountsRouterPicks:
    """A model whose step programs count their router's picks on the
    device (``forward``'s ``stats``); the runner fetches the counts
    behind the step's tokens and hands them back (``fetched_counts``).
    ``moe_pick_kinds`` names them in order; ``moe_pick_counter`` is the
    worker counter they add to (a row of ``metrics.WORKER_COUNTERS``)."""

    moe_pick_kinds: tuple[str, ...] = ()
    moe_pick_counter = "moe_picks"

    def fetched_counts(self, tail) -> tuple[dict, dict]:
        """(the ``runner.fetch`` span's attributes, the worker counters'
        increments) of one step's fetched pick counts."""
        counts = {k: int(n) for k, n in zip(self.moe_pick_kinds, tail)}
        return (
            {f"moe_picks_{kind}": n for kind, n in counts.items()},
            {self.moe_pick_counter: counts},
        )


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float
) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(
        dtype
    )


def linear(x: jax.Array, w, b: jax.Array | None = None) -> jax.Array:
    from vllm_distributed_tpu.ops.quant import quant_matmul

    return quant_matmul(x, w, b)


def rope_frequencies(
    head_dim: int,
    theta: float,
    *,
    rope_scaling: dict | None = None,
) -> jax.Array:
    """Inverse frequencies [head_dim // 2], with llama3/linear scaling."""
    inv_freq = 1.0 / (
        theta
        ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if not rope_scaling:
        return inv_freq
    rtype = rope_scaling.get("rope_type", rope_scaling.get("type", ""))
    if rtype == "linear":
        return inv_freq / float(rope_scaling["factor"])
    if rtype == "llama3":
        factor = float(rope_scaling["factor"])
        lo = float(rope_scaling.get("low_freq_factor", 1.0))
        hi = float(rope_scaling.get("high_freq_factor", 4.0))
        orig = float(rope_scaling.get("original_max_position_embeddings", 8192))
        wavelen = 2.0 * jnp.pi / inv_freq
        ratio = orig / wavelen
        smooth = jnp.clip((ratio - lo) / (hi - lo), 0.0, 1.0)
        scaled = jnp.where(
            wavelen > orig / lo,  # low-frequency band: full scaling
            inv_freq / factor,
            jnp.where(
                wavelen < orig / hi,  # high-frequency band: no scaling
                inv_freq,
                (1.0 - smooth) * inv_freq / factor + smooth * inv_freq,
            ),
        )
        return scaled
    # Unknown scaling types fall back to unscaled (logged by the loader).
    return inv_freq


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-magnitude correction for a context stretched
    ``factor`` times: ``0.1 · mscale · ln(factor) + 1`` (1 at no
    stretch)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(
    head_dim: int, theta: float, rope_scaling: dict
) -> tuple[int, int]:
    """The frequency pairs YaRN blends between, as published: pair
    ``lo`` is the last that turns ``beta_fast`` times over the original
    context (kept as it is), pair ``hi`` the first that turns
    ``beta_slow`` times (fully interpolated); floor and ceiling, clamped
    to the head's lanes."""
    orig = float(rope_scaling["original_max_position_embeddings"])

    def pair_of(turns: float) -> float:
        return (
            head_dim * math.log(orig / (turns * 2.0 * math.pi))
        ) / (2.0 * math.log(theta))

    lo = math.floor(pair_of(float(rope_scaling.get("beta_fast", 32))))
    hi = math.ceil(pair_of(float(rope_scaling.get("beta_slow", 1))))
    return max(lo, 0), min(hi, head_dim - 1)


def yarn_inv_freq(
    head_dim: int, theta: float, rope_scaling: dict
) -> jax.Array:
    """YaRN's inverse frequencies [head_dim // 2]: pair ``i`` keeps
    ``f_i = theta^(-2i/d)`` below the correction range, takes ``f_i /
    factor`` above it, and a linear blend of the two inside."""
    factor = float(rope_scaling["factor"])
    base = rope_frequencies(head_dim, theta)
    lo, hi = yarn_correction_range(head_dim, theta, rope_scaling)
    ramp = (jnp.arange(head_dim // 2, dtype=jnp.float32) - lo) / max(
        hi - lo, 0.001
    )
    keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return (base / factor) * (1.0 - keep) + base * keep


def apply_rope(
    x: jax.Array,  # [T, H, D]
    positions: jax.Array,  # [T]
    inv_freq: jax.Array,  # [D // 2]
) -> jax.Array:
    """HF-llama convention: rotate_half over the (front, back) halves."""
    angles = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = jnp.cos(angles)[:, None, :]  # [T, 1, D/2]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def apply_rope_interleaved(
    x: jax.Array,  # [T, H, D]
    positions: jax.Array,  # [T]
    inv_freq: jax.Array,  # [D // 2]
) -> jax.Array:
    """DeepSeek-V3 convention: lanes (2i, 2i+1) are the pair that
    frequency i rotates; the output keeps the lanes where they were."""
    angles = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = jnp.cos(angles)[:, None, :]  # [T, 1, D/2]
    sin = jnp.sin(angles)[:, None, :]
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    )
    return out.reshape(x.shape).astype(x.dtype)
