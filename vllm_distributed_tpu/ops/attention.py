"""Paged attention over a block-paged KV cache — pure-JAX reference path.

This is the TPU-native equivalent of the CUDA PagedAttention kernels the
reference inherits from the vLLM image (SURVEY.md §2.2).  The layout
contract shared by the allocator (engine/block_manager.py), the model
runner's KV scatter, and the kernels:

- KV pool: ONE combined array per layer of shape ``[2, num_pages,
  page_size, HD]`` — dim 0 is K/V, dims 1-2 address the token slot,
  and ``HD = num_kv_heads * head_dim`` is the flat head×dim lane axis.
  Rationale:
    * heads are stored unpadded and contiguous in HD, so the attention
      kernel computes on ``[BLK, F*D]`` tiles with ONE matmul + softmax
      chain per fold group instead of per-head slivers, and a 64-wide
      head model (Llama-1B class) pays no 2× lane-padding tax as a
      per-head ``[..., Hkv, 128]`` layout would;
    * a page is ``.at[kv, page]`` — a slice of the two MAJOR dims, so
      the kernel fetches it as one contiguous ``[page, HD]`` DMA per
      K/V plane.  (Mosaic cannot slice single rows of the tiled
      (page_size, HD) minor pair, which is why single steps write
      their rows with the XLA scatter of ``write_kv_pages`` and the
      fused scan stages its rows and flushes them by whole pages —
      ops/pallas/kv_flush.py);
    * token ``t`` of a request lives at page ``page_ids[t //
      page_size]``, row ``t % page_size``.
- A step's work is a flat token batch ``[T]`` spanning mixed prefill
  chunks and decodes; ``q_seq_ids``/``q_positions`` say which sequence
  and absolute position each query token has.

Everything is static-shape and jit-friendly: padding tokens carry
``q_seq_ids`` pointing at padded sequence rows whose ``seq_lens`` is 0,
so their attention rows are garbage that is never read.  The fast path
is the Pallas kernel in ops/pallas/; this reference is the correctness
oracle (tested against each other, SURVEY.md §4.2) and the CPU fallback.
"""

from __future__ import annotations

import math
from collections.abc import Sequence
from dataclasses import dataclass, field
from functools import partial, wraps

import jax
import jax.numpy as jnp

from vllm_distributed_tpu.models.common import SCOPE_ATTN, SCOPE_MLA_ABSORB

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def kv_pool_width(num_kv_heads: int, head_dim: int) -> int:
    """Flat lane width HD of the combined pool.

    No padding: every production shape (heads × 64/128-wide dims) is
    already a multiple of the 128-lane tile, and padding would both
    waste bytes and break per-head TP sharding of the flat lane axis
    for sub-128 test shapes (the pad would land in the last shard
    instead of spreading per head).  The Pallas kernel's single-fold
    fallback handles non-128-multiple widths.
    """
    return num_kv_heads * head_dim


def kv_pool_shape(
    num_pages: int, page_size: int, num_kv_heads: int, head_dim: int
) -> tuple[int, int, int, int]:
    return (
        2,
        num_pages,
        page_size,
        kv_pool_width(num_kv_heads, head_dim),
    )


def kv_scales_shape(
    num_pages: int, page_size: int, num_kv_heads: int
) -> tuple:
    """Scale plane of an int8 KV pool: one f32 scale PER (token, kv
    head), lane axis = kv heads.  Per-head (not per-token-row) scales
    are what make the quantized pool TP-shardable: the flat HD lane
    axis shards per head, so each shard's local absmax over its own
    heads' lanes IS the per-head scale — bit-identical to the
    unsharded computation, with the scale array sharding over the same
    lane axis (``tp`` must divide num_kv_heads, enforced at load)."""
    return (2, num_pages, page_size, num_kv_heads)


@dataclass(frozen=True)
class KVCacheLayout:
    """What a model states of its paged caches; the runner's pool,
    its page bytes, the fused scan's side buffers, the flush, the tier
    operations and page export/import read this and nothing else of the
    model.  Every cache is ``[planes, P, page, width]``: ``planes`` is 2
    for a key and a value of every KV head (``width`` their flat
    head×dim lanes, ``scale_width`` the lanes of an int8 pool's f32
    scale plane: one a KV head), 1 for a latent row that is key and
    value at once (``scale_width`` 0: such a row has no heads to scale
    by, and no int8 form).  How a pool shards over a mesh stays the
    model's ``kv_cache_spec()``."""

    num_caches: int
    planes: int
    width: int
    scale_width: int = 0

    @classmethod
    def by_heads(
        cls, num_layers: int, num_kv_heads: int, head_dim: int
    ) -> "KVCacheLayout":
        """One cache a layer: a key and a value plane of every KV
        head's lanes, an int8 pool scaled per (token, KV head)."""
        return cls(
            num_caches=num_layers,
            planes=2,
            width=kv_pool_width(num_kv_heads, head_dim),
            scale_width=num_kv_heads,
        )

    def token_bytes(self, itemsize: int, quantized: bool = False) -> int:
        """Bytes one token holds over all of the model's caches."""
        per_plane = self.width * itemsize
        if quantized:
            per_plane += self.scale_width * 4  # f32 scale row
        return self.num_caches * self.planes * per_plane

    def pool_shape(self, num_pages: int, page_size: int) -> tuple:
        return (self.planes, num_pages, page_size, self.width)

    def scales_shape(self, num_pages: int, page_size: int) -> tuple:
        return (self.planes, num_pages, page_size, self.scale_width)


@dataclass(frozen=True)
class StateKind:
    """One kind of state a sequence keeps: ``num_layers`` arrays ``[R,
    *shape]`` (one a layer that has such state), ``shape`` the state of
    one sequence in one layer (``(rows, width)`` for the rows a short
    convolution looks back on, ``(heads, head_dim, d_state)`` for a
    state-space state), ``dtype`` its stored type (None: the model's)."""

    name: str
    shape: tuple
    num_layers: int
    dtype: str | None = None

    def seq_bytes(self, model_dtype) -> int:
        """Bytes one sequence holds of this kind over all its layers."""
        itemsize = jnp.dtype(self.dtype or model_dtype).itemsize
        return self.num_layers * math.prod(self.shape) * itemsize


@dataclass(frozen=True)
class SeqStateLayout:
    """What a model states of the state it keeps a *sequence*, beside
    its paged caches: a list of state kinds (``StateKind``), each with
    a shape, a dtype and a count of layers of its own.  The runner
    allocates every kind's arrays at ``R`` rows, its sequence bucket; a
    request's row is the one its last sampled token has in the device
    carry (``CachedReqState.slot``), the same in every array.  The
    state is whatever length the sequence has, so it is no part of
    ``KVCacheLayout.token_bytes``: its bytes come off the pool once, at
    ``R`` rows.  Nothing clears a row when it changes hands: a model
    reads of it only what lies at a position >= 0 of the sequence that
    holds it now.

    Which models state which: ``Lfm2MoeForCausalLM`` one kind, ``conv``
    (the last two gated rows of every convolution layer, model dtype);
    ``FalconH1ForCausalLM`` two, ``conv`` (the last three rows that
    enter every layer's four-tap convolution) and ``ssm`` (every
    layer's state-space state, heads × head size × state size)."""

    kinds: tuple

    @classmethod
    def one_kind(
        cls, name: str, num_layers: int, rows: int, width: int
    ) -> "SeqStateLayout":
        """One kind of ``rows`` rows of ``width`` in the model's dtype."""
        return cls(kinds=(StateKind(name, (rows, width), num_layers),))

    @property
    def num_states(self) -> int:
        return sum(k.num_layers for k in self.kinds)

    def arrays(self, num_seqs: int, model_dtype) -> list[tuple]:
        """(shape, dtype) of every array at ``num_seqs`` rows, in the
        order ``SeqState.states`` holds them: kind by kind, each kind's
        layers in layer order."""
        return [
            ((num_seqs, *k.shape), jnp.dtype(k.dtype or model_dtype))
            for k in self.kinds
            for _ in range(k.num_layers)
        ]

    def by_kind(self, states: list) -> dict:
        """``SeqState.states`` split into each kind's per-layer list."""
        out, lo = {}, 0
        for k in self.kinds:
            out[k.name] = states[lo : lo + k.num_layers]
            lo += k.num_layers
        return out

    def bytes_by_kind(self, model_dtype) -> dict:
        """Bytes one sequence holds of each kind."""
        return {k.name: k.seq_bytes(model_dtype) for k in self.kinds}

    def seq_bytes(self, model_dtype) -> int:
        """Bytes one sequence holds over all of the model's states."""
        return sum(self.bytes_by_kind(model_dtype).values())


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class SeqState:
    """One step's view of the sequence state: the arrays, and which row
    of them each of the step's sequences holds.

      states:    the layout's arrays [R, *kind.shape], kind by kind
                 (``SeqStateLayout.arrays``; ``by_kind`` splits them)
      slots:     [S] int32 — row of each sequence of the step; a padding
                 or masked sequence points at R (past the end: what a
                 model writes for it is dropped)
      last_rows: [S] int32 — flat index of each sequence's last token of
                 the step (what is left behind is the state after it)
      max_q:     static — the step's longest-chunk bucket; 1 says every
                 sequence of the step has one token (the fused scan's
                 micro-steps, a decode-only single step), 0 that nothing
                 is known.  A model whose state costs more to walk in
                 chunks than to update by a token reads it; no array
                 depends on it.
    """

    states: list
    slots: jax.Array
    last_rows: jax.Array
    max_q: int = field(default=0, metadata=dict(static=True))


def quantize_kv_heads(
    k: jax.Array,  # [..., Hkv * D] flat rows (model dtype)
    num_kv_heads: int,
) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-(row, kv-head) int8 quantization.

    Returns (q [..., Hkv*D] int8, s [..., Hkv] f32) with
    row ≈ q * s[..., head_of_lane]."""
    d = k.shape[-1] // num_kv_heads
    kf = k.astype(jnp.float32).reshape(*k.shape[:-1], num_kv_heads, d)
    s = jnp.maximum(jnp.max(jnp.abs(kf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(kf / s[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(k.shape), s


def split_kv_pages(
    kv_pages, num_kv_heads: int, head_dim: int
) -> tuple[jax.Array, jax.Array]:
    """Views of the combined pool as per-head [P, page, Hkv, D] K and V.

    A quantized pool ((int8 data, per-head scales) tuple) dequantizes
    to f32."""
    if isinstance(kv_pages, tuple):
        data, scales = kv_pages
        _, p, page, hd = data.shape
        shape = (p, page, num_kv_heads, head_dim)
        deq = (
            data.astype(jnp.float32).reshape(2, *shape)
            * scales[..., None]
        )
        return deq[0], deq[1]
    _, p, page, hd = kv_pages.shape
    shape = (p, page, num_kv_heads, head_dim)
    return kv_pages[0].reshape(shape), kv_pages[1].reshape(shape)


def merge_kv_pages(k_pages: jax.Array, v_pages: jax.Array) -> jax.Array:
    """Inverse of split_kv_pages (test helper)."""
    p, page, hkv, d = k_pages.shape
    return jnp.stack(
        [
            k_pages.reshape(p, page, hkv * d),
            v_pages.reshape(p, page, hkv * d),
        ],
        axis=0,
    )


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class AttentionMetadata:
    """Per-step attention inputs shared by every layer.

    Shapes (all padded to bucketed static sizes):
      q_seq_ids:      [T] int32  — row of each query token in the seq batch
      q_positions:    [T] int32  — absolute position of each query token
      slot_mapping:   [T] int32  — flat KV slot each token's K/V is written to
                                   (padding tokens point into reserved page 0)
      block_tables:   [S, max_pages] int32 — page ids per sequence (0-padded)
      seq_lens:       [S] int32  — total context length per sequence
                                   (computed + scheduled this step; 0 = pad row)
      logits_indices: [S] int32  — flat token index whose hidden state is
                                   sampled for each sequence
      chunk_starts:   [S] int32  — absolute position of each sequence's
                                   first query token this step (= its
                                   num_computed_tokens before the step)
    """

    q_seq_ids: jax.Array
    q_positions: jax.Array
    slot_mapping: jax.Array
    block_tables: jax.Array
    seq_lens: jax.Array
    logits_indices: jax.Array
    chunk_starts: jax.Array


def write_kv_pages(
    kv_pages,  # [2, P, page, HD] or (int8 pool, scales) tuple
    k: jax.Array,  # [T, Hkv, D]
    v: jax.Array | None,  # None: a one-plane pool [1, P, page, W]
    slot_mapping: jax.Array,
):
    """Scatter this step's K/V into the combined paged pool (quantizing
    on write when the pool is int8).

    Every single step's writer, and the oracle of the staged flush
    (ops/pallas/kv_flush.py), which takes its place inside the fused
    decode scan: XLA does not keep this scatter in place there at large
    pool sizes.
    """
    if isinstance(kv_pages, tuple):
        data, scales = kv_pages
        _, _, page_size, hd = data.shape
        hkv = scales.shape[-1]
        t = k.shape[0]
        q_k, s_k = quantize_kv_heads(k.reshape(t, -1), hkv)
        q_v, s_v = quantize_kv_heads(v.reshape(t, -1), hkv)
        if q_k.shape[-1] < hd:  # sub-tile pools pad HD (like below)
            pad = [(0, 0), (0, hd - q_k.shape[-1])]
            q_k = jnp.pad(q_k, pad)
            q_v = jnp.pad(q_v, pad)
        pages = slot_mapping // page_size
        rows = slot_mapping % page_size
        data = data.at[0, pages, rows].set(q_k)
        data = data.at[1, pages, rows].set(q_v)
        scales = scales.at[0, pages, rows].set(s_k)
        scales = scales.at[1, pages, rows].set(s_v)
        return (data, scales)
    _, _, page_size, hd = kv_pages.shape
    t, hkv, d = k.shape
    if v is None:  # a one-plane (latent) pool: the row is key and value
        rows = k.reshape(t, hkv * d).astype(kv_pages.dtype)
        return kv_pages.at[
            0, slot_mapping // page_size, slot_mapping % page_size
        ].set(rows)
    k = k.reshape(t, hkv * d).astype(kv_pages.dtype)
    v = v.reshape(t, hkv * d).astype(kv_pages.dtype)
    if hkv * d < hd:  # sub-tile pools pad HD to the lane tile
        pad = [(0, 0), (0, hd - hkv * d)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    pages = slot_mapping // page_size
    rows = slot_mapping % page_size
    kv_pages = kv_pages.at[0, pages, rows].set(k)
    kv_pages = kv_pages.at[1, pages, rows].set(v)
    return kv_pages


@partial(jax.jit, static_argnames=("scale", "soft_cap", "num_kv_heads"))
def paged_attention_reference(
    q: jax.Array,  # [T, Hq, D]
    kv_pages: jax.Array,  # [2, P, page, HD]
    metadata: AttentionMetadata,
    *,
    scale: float,
    soft_cap: float | None = None,
    num_kv_heads: int | None = None,
    side_kv: jax.Array | None = None,  # [S, 2, K, HD] staged decode rows
    side_len: jax.Array | None = None,  # [1] int32
) -> jax.Array:
    """Causal attention of flat query tokens against their sequences' paged
    KV history.  O(T × max_ctx) with full gathers — the oracle, not the
    fast path.

    ``side_kv``/``side_len``: staged decode rows holding positions
    ``seq_lens[s] + j`` (seq_lens is the pool-resident length when
    staging) — see the Pallas kernel's docstring.
    """
    t, hq, d = q.shape
    hkv = num_kv_heads if num_kv_heads is not None else hq
    k_pages, v_pages = split_kv_pages(kv_pages, hkv, d)
    _, page_size, _, _ = k_pages.shape
    s, max_pages = metadata.block_tables.shape
    groups = hq // hkv
    max_ctx = max_pages * page_size

    # Gather each sequence's KV: [S, max_ctx, Hkv, D].
    k_all = k_pages[metadata.block_tables].reshape(s, max_ctx, hkv, d)
    v_all = v_pages[metadata.block_tables].reshape(s, max_ctx, hkv, d)

    seq_lens_tok = metadata.seq_lens[metadata.q_seq_ids]  # [T]
    ctx_pos = jnp.arange(max_ctx, dtype=jnp.int32)
    valid = ctx_pos[None, :] <= metadata.q_positions[:, None]  # causal
    valid &= ctx_pos[None, :] < seq_lens_tok[:, None]

    if side_kv is not None:
        k_blk = side_kv.shape[2]
        side = side_kv[..., : hkv * d].reshape(s, 2, k_blk, hkv, d)
        k_all = jnp.concatenate([k_all, side[:, 0]], axis=1)
        v_all = jnp.concatenate([v_all, side[:, 1]], axis=1)
        j = jnp.arange(k_blk, dtype=jnp.int32)
        side_pos = seq_lens_tok[:, None] + j[None, :]  # [T, K]
        side_valid = (
            (j[None, :] < side_len[0])
            & (side_pos <= metadata.q_positions[:, None])
            & (seq_lens_tok[:, None] > 0)
        )
        valid = jnp.concatenate([valid, side_valid], axis=1)

    # Per query token, its sequence's KV: [T, C, Hkv, D].
    k_tok = k_all[metadata.q_seq_ids]
    v_tok = v_all[metadata.q_seq_ids]

    qg = q.reshape(t, hkv, groups, d).astype(jnp.float32)
    scores = jnp.einsum(
        "thgd,tchd->thgc", qg, k_tok.astype(jnp.float32)
    ) * scale  # [T, Hkv, G, C]
    if soft_cap is not None:
        scores = jnp.tanh(scores / soft_cap) * soft_cap

    scores = jnp.where(valid[:, None, None, :], scores, DEFAULT_MASK_VALUE)

    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.exp(scores)
    denom = jnp.sum(probs, axis=-1, keepdims=True)
    probs = probs / jnp.maximum(denom, 1e-30)

    out = jnp.einsum("thgc,tchd->thgd", probs, v_tok.astype(jnp.float32))
    return out.reshape(t, hq, d).astype(q.dtype)


@partial(jax.jit, static_argnames=("scale", "value_width"))
def mla_attention_reference(
    q: jax.Array,  # [T, H, W] absorbed queries (W = latent + rope lanes)
    kv_pages: jax.Array,  # [1, P, page, WP]: one latent row a token, its
    # first W lanes live (the rest pads the row to whole lane tiles)
    metadata: AttentionMetadata,
    *,
    scale: float,
    value_width: int,
    side_kv: jax.Array | None = None,  # [S, 1, K, WP] staged decode rows
    side_len: jax.Array | None = None,  # [1] int32
) -> jax.Array:
    """Latent (absorbed MLA) attention of flat query tokens against
    their sequences' paged latent rows: every head scores the same row
    ``[c, k_rope]`` over all ``W`` lanes, and the value is the row's
    first ``value_width`` lanes.  Returns [T, H, value_width].  The
    oracle and XLA path of ops/pallas/mla_attention.py; same contract
    on ``side_kv``/``side_len`` as ``paged_attention_reference``."""
    t, h, w = q.shape
    _, _, page_size, _ = kv_pages.shape
    s, max_pages = metadata.block_tables.shape
    max_ctx = max_pages * page_size
    rows = kv_pages[0][metadata.block_tables][..., :w].reshape(
        s, max_ctx, w
    )
    seq_lens_tok = metadata.seq_lens[metadata.q_seq_ids]  # [T]
    ctx_pos = jnp.arange(max_ctx, dtype=jnp.int32)
    valid = ctx_pos[None, :] <= metadata.q_positions[:, None]
    valid &= ctx_pos[None, :] < seq_lens_tok[:, None]
    if side_kv is not None:
        k_blk = side_kv.shape[2]
        rows = jnp.concatenate([rows, side_kv[:, 0, :, :w]], axis=1)
        j = jnp.arange(k_blk, dtype=jnp.int32)
        side_pos = seq_lens_tok[:, None] + j[None, :]
        side_valid = (
            (j[None, :] < side_len[0])
            & (side_pos <= metadata.q_positions[:, None])
            & (seq_lens_tok[:, None] > 0)
        )
        valid = jnp.concatenate([valid, side_valid], axis=1)
    rows_tok = rows[metadata.q_seq_ids].astype(jnp.float32)  # [T, C, W]
    scores = jnp.einsum(
        "thw,tcw->thc", q.astype(jnp.float32), rows_tok
    ) * scale
    scores = jnp.where(valid[:, None, :], scores, DEFAULT_MASK_VALUE)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.maximum(
        jnp.sum(probs, axis=-1, keepdims=True), 1e-30
    )
    out = jnp.einsum("thc,tcv->thv", probs, rows_tok[..., :value_width])
    return out.astype(q.dtype)


def mla_absorb_queries(
    q_nope: jax.Array,  # [T, H, dn]
    q_rope: jax.Array,  # [T, H, dr]
    w_uk: jax.Array,  # [H, dn, C]
) -> jax.Array:
    """Queries into the latent space: ``[q_nope · W_uk, q_rope]``
    [T, H, C + dr], what scores a cached row ``[c, k_rope]``."""
    q_lat = jnp.einsum(
        "thd,hdc->thc", q_nope, w_uk.astype(q_nope.dtype),
        preferred_element_type=jnp.float32,
    ).astype(q_nope.dtype)
    return jnp.concatenate([q_lat, q_rope], axis=-1)


def mla_unabsorb_outputs(
    o_lat: jax.Array,  # [T, H, C] attended latents
    w_uv: jax.Array,  # [H, C, dv]
) -> jax.Array:
    """Attended latents back out of the latent space: [T, H, dv]."""
    return jnp.einsum(
        "thc,hcv->thv", o_lat, w_uv.astype(o_lat.dtype),
        preferred_element_type=jnp.float32,
    ).astype(o_lat.dtype)


def mla_sublayer_attention(
    attn_fn,
    q_nope: jax.Array,  # [T, H, dn]
    q_rope: jax.Array,  # [T, H, dr], rotated
    c: jax.Array,  # [T, C] this step's latents, as cached
    k_rope: jax.Array,  # [T, dr] this step's rope keys, as cached
    w_uk: jax.Array,  # [H, dn, C]
    w_uv: jax.Array,  # [H, C, dv]
    kv_pages,  # the sublayer's cache, this step's rows written
    metadata: AttentionMetadata,
    *,
    scale: float,
) -> jax.Array:
    """One MLA sublayer's attention, from head space to head space: the
    model's one attention call.  Returns what ``W_o`` takes once
    flattened: [T, H, dv], or [T, H·dv] already.

    An ``attn_fn`` whose step can tell rows that need no cache from rows
    that do brings ``attn_fn.from_heads`` (``bind_max_q`` below, for
    ops/pallas/mla_attention.py in a chunked step) and gets the pieces.
    Every other ``attn_fn`` has ``mla_attention_reference``'s contract
    (this file's XLA path, the fused scan's staged call, a decode-only
    step) and gets the absorbed form around it: queries into the latent
    space, the attended latents back out."""
    from_heads = getattr(attn_fn, "from_heads", None)
    if from_heads is not None:
        # whatever it runs outside a scope of its own (the copies XLA
        # lays round its conditional) is attention's glue
        with jax.named_scope(SCOPE_ATTN):
            return from_heads(
                q_nope, q_rope, c, k_rope, w_uk, w_uv, kv_pages, metadata,
                scale=scale,
            )
    with jax.named_scope(SCOPE_MLA_ABSORB):
        q_abs = mla_absorb_queries(q_nope, q_rope, w_uk)
    with jax.named_scope(SCOPE_ATTN):
        o_lat = attn_fn(
            q_abs, kv_pages, metadata, scale=scale, value_width=c.shape[-1]
        )
    with jax.named_scope(SCOPE_MLA_ABSORB):
        return mla_unabsorb_outputs(o_lat, w_uv)


@dataclass(frozen=True)
class StepShape:
    """What the runner knows of one step it dispatches.  It hands this
    to the attention function it picked and to the model
    (``step_counts``); each answers with ``(attributes, increments)``:
    what the step's ``runner.dispatch`` span says of it, and what those
    add to the worker's counters (counter -> label -> n, a counter a
    row of ``metrics.WORKER_COUNTERS``).  The runner merges the first
    and passes the second on unread; who has nothing to say has no
    ``step_counts``."""

    # the step program's kind, as ``vllm:xla_compiles_total`` counts it:
    # "prefill" (a single step: chunks and one-token rows alike) |
    # "decode" (a fused scan) | "spec" (a verify step)
    kind: str
    num_new: Sequence[int]  # each row's new tokens
    starts: Sequence[int]  # each row's start: its tokens already cached
    # the padded token rows a layer sees: a single step's token bucket,
    # a scan's sequence bucket
    rows_pad: int
    seqs_pad: int
    max_q_pad: int  # the longest-chunk bucket; 1 in a scan
    pages_pad: int
    page_size: int
    cache_itemsize: int  # bytes an element of the pool
    head_shards: int  # the mesh's tp


def bind_step_counts(attn_fn, model):
    """``attn_fn`` as picked for ``model``: where the function's module
    counts what a step of it walks (``step_counts_for``, the host-side
    twins of the kernel's lists), the function with ``step_counts``
    bound to the model's widths beside its other attributes; any other
    function as it is."""
    counts_for = getattr(attn_fn, "step_counts_for", None)
    if counts_for is None:
        return attn_fn
    bound = wraps(attn_fn)(partial(attn_fn))
    bound.step_counts = counts_for(model)
    return bound


def bind_max_q(attn_fn, max_q: int):
    """``attn_fn`` for a program whose longest chunk is in the bucket
    ``max_q``, where the kernel's grid depends on it (``needs_max_q``);
    its head-space entry (``from_heads``: chunked steps only) bound
    alike."""
    if not getattr(attn_fn, "needs_max_q", False):
        return attn_fn
    bound = partial(attn_fn, max_q=max_q)
    from_heads = getattr(attn_fn, "from_heads", None)
    if from_heads is not None and max_q > 1:
        bound.from_heads = partial(from_heads, max_q=max_q)
    return bound
