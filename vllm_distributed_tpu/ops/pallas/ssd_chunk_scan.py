"""Pallas chunked state-space scan over the flat ragged batch: what
``ops/ssm.ssd_chunk_scan`` computes (its docstring is the contract), with
each state row the step holds read from HBM once, kept in float32 in
VMEM while the row's tokens are walked, and written once, in place.

**The grid is (head block, sequence slot in batch order).**  A grid
step is one sequence's state for ``hb`` heads of one group: its block
``[1, hb, P, N]`` comes in through a ``BlockSpec`` whose index map reads
the sequence's row from scalar prefetch, is widened into a float32
scratch (zeros for a chunk at position 0, whatever the row holds), is
walked, and goes out through the aliased output.  A slot that holds no
live sequence points at the row of the live sequence before it, so no
block index changes and nothing is fetched or written for it; slots
before the first live sequence copy the block they point at through
(a step with no live sequence rewrites the last row with itself).

**The walk.**  A sequence of several tokens walks the aligned
``chunk``-token windows of the flat batch it has tokens in (a
``fori_loop`` of dynamic bounds), masked to its own tokens: within a
window the masked product ``(C Bᵀ ⊙ L) · (Δ x)``, the carried state's
part ``C · S`` decayed to each token, and ``S ← exp(Σ a) S + Σ_s
exp(Σ_{r>s} a_r) Δ_s x_s ⊗ B_s`` to the sequence's last token of the
window.  A sequence of one token (a running sequence that rides with
the prompts) takes one 16-token window and no ``chunk x chunk`` product:
``S ← exp(a) S + Δ x ⊗ B``, ``y = exp(a) C · S + (C · B) Δ x``.  ``y``
accumulates into a block that stays resident across the sequence axis
(sequences' tokens are disjoint; padding tokens stay zero).

Cumulative sums and decays are float32 and come in from XLA (they are
small); the matrix products take operands in the activations' dtype
and accumulate in float32; the carried state stays float32 across
windows and is rounded to the state's dtype once, at the write.  No
elementwise arithmetic in bfloat16.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Tokens of the window a one-token sequence takes: one packed bfloat16
# tile of rows.
ROW_WINDOW = 16
# Heads a grid step: a divisor of a group's heads, so a block reads one
# group's B and C.  Fixed, so a program is one a token bucket.
_HEADS_PER_STEP = 8
_VMEM_HEADROOM = 8 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))

# what a slot's grid step does (scalar prefetch ``mode``)
_IDLE, _LIVE, _COPY = 0, 1, 2
_PREFETCHED = 5  # row, first, last, carried, mode


def accepts(head_dim: int, state: int, tokens: int, chunk: int) -> bool:
    """Whether the compiled kernel is written for these shapes: whole
    lane tiles of ``P`` and ``N``, windows of whole lane tiles (a
    window's sums are sliced along lanes), a token bucket of whole
    windows.  Interpreted, a window may be any multiple of
    ``ROW_WINDOW`` (tests walk windows of 16)."""
    return (
        head_dim % _LANES == 0
        and state % _LANES == 0
        and chunk % _LANES == 0
        and tokens % chunk == 0
    )


def walked_tokens(chunk_lens, chunk: int) -> int:
    """Token slots the kernel's matrix products run over for a step
    whose sequences have ``chunk_lens`` tokens, in batch order (host
    side: what ``ssm_tokens_tiled`` counts on this path)."""
    walked = lo = 0
    for n in chunk_lens:
        if n == 1:
            walked += ROW_WINDOW
        elif n > 1:
            walked += chunk * ((lo + n - 1) // chunk - lo // chunk + 1)
        lo += n
    return walked


def _kernel(
    # scalar prefetch, a sequence slot each
    row_ref,  # [S] the state row its grid steps point at
    first_ref,  # [S] flat index of its first token
    last_ref,  # [S] ... of its last
    carried_ref,  # [S] 1: it reads its row (its chunk starts past 0)
    mode_ref,  # [S] _IDLE | _LIVE | _COPY
    # blocks
    xdt_ref,  # [T, hb·P]  Δ·x in the activations' dtype
    b_ref,  # [T, N]  the block's group's B
    c_ref,  # [T, N]
    cum_ref,  # [T, 2·hb] f32: Σ a over the window through t | a_t
    cum_t_ref,  # [hb, T] f32: the first half, tokens on lanes
    state_ref,  # [1, hb, P, N]
    y_ref,  # [T, hb·P] f32, resident
    out_ref,  # [1, hb, P, N], aliased to the state
    s_scr,  # [hb, P, N] f32
    *,
    heads: int,
    head_dim: int,
    chunk: int,
):
    q = pl.program_id(1)
    hb, p, ln = heads, head_dim, chunk
    f32 = jnp.float32
    first, last = first_ref[q], last_ref[q]
    carried = carried_ref[q] > 0
    mode = mode_ref[q]
    cd = xdt_ref.dtype

    @pl.when(q == 0)
    def _zero_y():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(mode == _COPY)
    def _copy_through():
        out_ref[...] = state_ref[...]

    def cols(i):
        return slice(i * p, (i + 1) * p)

    @pl.when((mode == _LIVE) & (first == last))
    def _one_token():
        w0 = pl.multiple_of((first // ROW_WINDOW) * ROW_WINDOW, ROW_WINDOW)
        win = pl.ds(w0, ROW_WINDOW)
        here = (
            w0 + jax.lax.broadcasted_iota(jnp.int32, (ROW_WINDOW, 1), 0)
        ) == first
        b_w, c_w = b_ref[win, :], c_ref[win, :]
        a_w = cum_ref[win, :]
        # C·B a token of the window (float32: the operands' products are
        # exact), for y = exp(a) C·S + (C·B) Δx
        cb = jnp.sum(c_w.astype(f32) * b_w.astype(f32), axis=1, keepdims=True)
        for i in range(hb):
            decay = jnp.exp(jnp.sum(
                jnp.where(here, a_w[:, hb + i:hb + i + 1], 0.0),
                axis=0, keepdims=True,
            ))  # [1, 1]
            xw = jnp.where(here, xdt_ref[win, cols(i)], jnp.zeros((), cd))
            # Both products read what came in, neither waits for the
            # other or for the new state (at 64 such rows a step the
            # kernel then runs at the pace of the rows' DMAs: PERF.md
            # §6, PR 51).
            y_old = jax.lax.dot_general(
                c_w, state_ref[0, i].astype(cd), _NT,
                preferred_element_type=f32,
            )
            y_ref[win, cols(i)] += jnp.where(
                here,
                jnp.where(carried, decay * y_old, 0.0) + cb * xw.astype(f32),
                0.0,
            )
            old = jnp.where(carried, state_ref[0, i].astype(f32), 0.0)
            out_ref[0, i] = (
                decay * old
                + jax.lax.dot_general(xw, b_w, _TN, preferred_element_type=f32)
            ).astype(out_ref.dtype)

    @pl.when((mode == _LIVE) & (first != last))
    def _walk():
        for i in range(hb):
            s_scr[i] = jnp.where(carried, state_ref[0, i].astype(f32), 0.0)
        t_col = jax.lax.broadcasted_iota(jnp.int32, (ln, 1), 0)
        t_row = jax.lax.broadcasted_iota(jnp.int32, (1, ln), 1)
        causal = t_row <= t_col  # [L, L]: column s at or before row t

        def window(k, carry):
            w0 = pl.multiple_of(k * ln, ln)
            win = pl.ds(w0, ln)
            in_col = (w0 + t_col >= first) & (w0 + t_col <= last)
            in_row = (w0 + t_row >= first) & (w0 + t_row <= last)
            mask = causal & in_col & in_row
            # the sequence's first token where it lies in this window,
            # its last token of this window
            at_first = (w0 + t_col) == first
            at_end = (w0 + t_col) == jnp.minimum(last, w0 + ln - 1)
            b_w, c_w = b_ref[win, :], c_ref[win, :]
            scores = jax.lax.dot_general(
                c_w, b_w, _NT, preferred_element_type=f32
            )  # [L, L]
            a_w = cum_ref[win, :]
            cum_rows = cum_t_ref[:, win]
            for i in range(hb):
                cum = a_w[:, i:i + 1]  # [L, 1]
                # Σ a before the first token (0 where the sequence came
                # in over the window's edge), through the last
                before = jnp.sum(
                    jnp.where(at_first, cum - a_w[:, hb + i:hb + i + 1], 0.0),
                    axis=0, keepdims=True,
                )
                end = jnp.sum(
                    jnp.where(at_end, cum, 0.0), axis=0, keepdims=True
                )
                x_w = xdt_ref[win, cols(i)]
                decay = jnp.exp(
                    jnp.where(mask, cum - cum_rows[i:i + 1, :], -jnp.inf)
                )
                y = jax.lax.dot_general(
                    (decay * scores).astype(cd), x_w, _NN,
                    preferred_element_type=f32,
                )
                s_old = s_scr[i]
                w_in = jnp.exp(jnp.where(in_col, cum - before, -jnp.inf))
                y = y + w_in * jax.lax.dot_general(
                    c_w, s_old.astype(cd), _NT, preferred_element_type=f32
                )
                y_ref[win, cols(i)] += y
                w_end = jnp.exp(jnp.where(in_col, end - cum, -jnp.inf))
                s_scr[i] = jnp.exp(end - before) * s_old + jax.lax.dot_general(
                    (x_w.astype(f32) * w_end).astype(cd), b_w, _TN,
                    preferred_element_type=f32,
                )
            return carry

        jax.lax.fori_loop(first // ln, last // ln + 1, window, None)
        for i in range(hb):
            out_ref[0, i] = s_scr[i].astype(out_ref.dtype)


def heads_per_step(group_heads: int) -> int:
    """Heads a grid step takes: the largest divisor of a group's heads
    up to ``_HEADS_PER_STEP``."""
    return max(
        d for d in range(1, min(_HEADS_PER_STEP, group_heads) + 1)
        if group_heads % d == 0
    )


def _vmem_limit_bytes(t, hb, p, n, ln, itemsize, state_itemsize) -> int:
    """The limit the call states: the pipelined (double-buffered)
    blocks, the float32 state, and a head's float32 temporaries (the
    window's decays and weights, the state's products), which Mosaic
    may keep for more than one head of the unrolled loop."""
    blocks = 2 * (
        t * hb * p * (itemsize + 4)  # Δx in, y out
        + 2 * t * n * itemsize  # B, C
        + t * _LANES * 4  # the sums, lane-padded
        + max(hb, 8) * t * 4
        + 2 * hb * p * n * state_itemsize  # the state in and out
    )
    state = hb * p * n * 4
    temps = 2 * 4 * (4 * ln * ln + 3 * ln * p + 3 * p * n)
    return blocks + state + temps + _VMEM_HEADROOM


def ssd_chunk_scan(
    x: jax.Array,  # [T, H, P]
    dt: jax.Array,  # [T, H] float32, after the softplus
    a: jax.Array,  # [H] float32, negative
    b: jax.Array,  # [T, G, N]
    c: jax.Array,  # [T, G, N]
    d_skip: jax.Array,  # [H] float32
    state: jax.Array,  # [R, H, P, N]
    *,
    seq_ids: jax.Array,  # [T] sorted; padding = S  (unused: the
    # sequences' first and last tokens say the same)
    positions: jax.Array,  # [T]
    chunk_starts: jax.Array,  # [S]
    slots: jax.Array,  # [S]: each sequence's state row (>= R: none)
    last_rows: jax.Array,  # [S]: flat index of its last token
    chunk: int,
    heads: int | None = None,
    scope=contextlib.nullcontext,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``ops/ssm.ssd_chunk_scan`` with the recurrence in one kernel:
    (y [T, H, P] float32, the state with the step's sequences' rows
    rewritten in place).  What is small (the windows' cumulative sums,
    ``Δ·x``, the sequences' scalars) is made here in XLA, in front of
    the call, and so is ``D·x`` behind it: both under ``scope()`` (a
    context manager: the caller's ``jax.named_scope``s), the call
    itself under none of them, so that a trace files the kernel's time
    once, under its name."""
    del seq_ids
    with scope():
        operands = _operands(
            x, dt, a, b, c, state, positions, chunk_starts, slots,
            last_rows, chunk, heads,
        )
    y, state = _call(*operands, chunk=chunk, interpret=interpret)
    with scope():
        t, h, p = x.shape
        y = y.reshape(t, h, p) + d_skip[:, None] * x.astype(jnp.float32)
    return y, state


def _operands(
    x, dt, a, b, c, state, positions, chunk_starts, slots, last_rows,
    chunk, heads,
) -> tuple:
    """The call's operands: the five scalars a sequence slot, then Δ·x,
    B and C as flat views, the windows' sums by head block in both
    orientations, the state."""
    t, h, p = x.shape
    r, n = state.shape[0], state.shape[-1]
    g = b.shape[1]
    s = slots.shape[0]
    assert chunk % ROW_WINDOW == 0 and t % chunk == 0, (t, chunk)
    hb = heads or heads_per_step(h // g)
    assert (h // g) % hb == 0, (h, g, hb)
    nb = h // hb
    f32, i32 = jnp.float32, jnp.int32

    da = dt * a  # [T, H]
    cum = jnp.cumsum(da.reshape(t // chunk, chunk, h), axis=1).reshape(t, h)

    def by_block(v):  # [T, H] -> [H/hb, T, hb]
        return v.reshape(t, nb, hb).transpose(1, 0, 2)

    cum_b = by_block(cum)
    sums = jnp.concatenate([cum_b, by_block(da)], axis=-1)
    sums_t = cum_b.transpose(0, 2, 1)  # [H/hb, hb, T]
    xdt = (x.astype(f32) * dt[..., None]).astype(x.dtype).reshape(t, h * p)

    # every sequence's first token of the step, from its last
    live = slots < r
    first_tok = jnp.clip(
        last_rows - (positions[last_rows] - chunk_starts), 0, t - 1
    )
    idx = jnp.arange(s, dtype=i32)
    latest = jax.lax.cummax(jnp.where(live, idx, -1))
    points_at = jnp.where(latest >= 0, latest, jnp.argmax(live).astype(i32))
    # (no live sequence at all: slot 0's row is past the rows, and every
    # slot copies the last row through)
    row = jnp.minimum(slots[points_at], r - 1)
    mode = jnp.where(live, _LIVE, jnp.where(latest < 0, _COPY, _IDLE))

    return (
        row.astype(i32), first_tok.astype(i32), last_rows.astype(i32),
        (chunk_starts > 0).astype(i32), mode.astype(i32),
        xdt, b.reshape(t, g * n), c.reshape(t, g * n), sums, sums_t, state,
    )


def _call(
    row, first, last, carried, mode, xdt, b, c, sums, sums_t, state,
    *, chunk: int, interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """(y [T, H·P] float32 without ``D·x``, the state rewritten in
    place)."""
    t, n = b.shape[0], state.shape[-1]
    h, p = state.shape[1], state.shape[2]
    g, s = b.shape[1] // n, row.shape[0]
    nb, hb = sums_t.shape[0], sums_t.shape[1]

    def tokens_map(hb_i, q, *_):
        return (0, hb_i)

    def group_map(hb_i, q, *_):
        return (0, hb_i * hb // (h // g))

    def sums_map(hb_i, q, *_):
        return (hb_i, 0, 0)

    def state_map(hb_i, q, row, *_):
        return (row[q], hb_i, 0, 0)

    kernel = functools.partial(_kernel, heads=hb, head_dim=p, chunk=chunk)
    return pl.pallas_call(
        kernel,
        name="vdt_ssd_chunk_scan",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=_PREFETCHED,
            grid=(nb, s),
            in_specs=[
                pl.BlockSpec((t, hb * p), tokens_map),
                pl.BlockSpec((t, n), group_map),
                pl.BlockSpec((t, n), group_map),
                pl.BlockSpec((None, t, 2 * hb), sums_map),
                pl.BlockSpec((None, hb, t), sums_map),
                pl.BlockSpec((1, hb, p, n), state_map),
            ],
            out_specs=[
                pl.BlockSpec((t, hb * p), tokens_map),
                pl.BlockSpec((1, hb, p, n), state_map),
            ],
            scratch_shapes=[pltpu.VMEM((hb, p, n), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((t, h * p), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # the state: the sixth operand behind the five prefetched
        input_output_aliases={_PREFETCHED + 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(
                t, hb, p, n, chunk, jnp.dtype(xdt.dtype).itemsize,
                jnp.dtype(state.dtype).itemsize,
            ),
        ),
        interpret=interpret,
    )(row, first, last, carried, mode, xdt, b, c, sums, sums_t, state)
