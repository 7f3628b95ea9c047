"""The selective state-space recurrence of a Mamba-2 mixer over the
runner's flat ragged batch — plain ``jax.numpy``, no loop that reaches
XLA as a ``while``.

Per head ``h`` (``P`` lanes), with the group ``g(h)`` that shares its
``B`` and ``C`` (``N`` wide) and ``a_t = Δ_t · A`` (``A < 0``)::

    S_t = exp(a_t) · S_{t-1} + Δ_t · x_t ⊗ B_t          S ∈ R^{P×N}
    y_t = S_t · C_t + D · x_t

Two forms of it:

``ssd_chunk_scan``  a single step's flat batch of ragged prompt chunks
    (and the one-token rows of running sequences that ride with them),
    in tiles of ``chunk`` tokens of the *batch*, so a tile may hold the
    end of one sequence, several whole ones and the start of another
    (masked by ``q_seq_ids``).  Within a tile the masked product ``(C
    Bᵀ ⊙ L) · (Δ x)`` with ``L_ts = exp(Σ_{s<r≤t} a_r)`` between tokens
    of one sequence; between tiles the state of the one sequence that
    can cross a tile's edge, ``R ← exp(Σ a) R + Σ_s exp(Σ_{r>s} a_r) Δ_s
    x_s ⊗ B_s``, unrolled over the (at most 16) tiles.  A sequence's
    *stored* state enters where its chunk starts — by linearity as one
    more term, computed a state row at a time over the tile its chunk
    starts in — and is zero for a chunk at position 0, whatever the row
    holds.  What a sequence leaves behind is computed a row at a time
    over the tile its chunk ends in, and written in place.  Cumulative
    sums and decays are float32; the matrix products take operands in
    the activations' dtype and accumulate in float32.
``ssm_update``  one token a sequence (the fused scan's micro-steps, a
    decode-only single step): one multiply-add over the state rows and
    ``y = S · C + D · x``.  The rows are walked in *row* order — the
    small per-token inputs are permuted to the rows, never the state to
    the tokens — so the state is read once and written once, in place.

Both leave the rows of sequences the step does not hold untouched: a
padding or masked token's row index lies past the end.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def ragged_causal_conv(
    z: jax.Array,  # [T, W]: what enters the convolution (and the state)
    prior: jax.Array,  # [T, K-1, W]: each token's sequence's stored rows
    off: jax.Array,  # [T, 1]: the token's offset in its chunk
    pos: jax.Array,  # [T, 1]: its absolute position
    taps: jax.Array,  # [K, W] float32; taps[K-1] multiplies z_t
) -> tuple[jax.Array, list]:
    """A depthwise causal convolution of ``K`` taps over a flat batch of
    ragged chunks: (Σ_j taps[K-1-j] ⊙ z_{t-j} [T, W] float32, [z_{t-1},
    …, z_{t-K+1}]).  A predecessor comes from the batch where it lies
    in the same chunk, from the stored rows ``(z_{n-K+1}, …, z_{n-1})``
    (``n`` the chunk's start) where it lies before it, and is zero
    before position 0."""
    t, k = z.shape[0], taps.shape[0]
    zp = jnp.pad(z, ((k - 1, 0), (0, 0)))
    out = taps[k - 1] * z.astype(jnp.float32)
    prevs = []
    for j in range(1, k):
        stored = prior[:, k - 1 - j]  # the chunk's first token
        for o in range(1, j):
            stored = jnp.where(off == o, prior[:, k - 1 - j + o], stored)
        prev = jnp.where(
            off >= j,
            zp[k - 1 - j : k - 1 - j + t],
            jnp.where(pos >= j, stored, 0),
        )
        out = out + taps[k - 1 - j] * prev.astype(jnp.float32)
        prevs.append(prev)
    return out, prevs


def _rows_of(slots: jax.Array, num_rows: int) -> tuple[jax.Array, jax.Array]:
    """The inverse of ``slots`` [S] (state row of each sequence or
    token; ``num_rows`` or more = none): (which sequence holds each row
    [R], clamped into range; whether any does [R])."""
    n = slots.shape[0]
    inv = jnp.full((num_rows,), n, jnp.int32).at[slots].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop"
    )
    return jnp.minimum(inv, n - 1), inv < n


def _grouped(x: jax.Array, groups: int) -> jax.Array:
    """[..., H, P] → [..., G, H/G, P]."""
    *lead, h, p = x.shape
    return x.reshape(*lead, groups, h // groups, p)


def ssm_update(
    x: jax.Array,  # [T, H, P]
    dt: jax.Array,  # [T, H] float32, after the softplus
    a: jax.Array,  # [H] float32, negative
    b: jax.Array,  # [T, G, N]
    c: jax.Array,  # [T, G, N]
    d_skip: jax.Array,  # [H] float32
    state: jax.Array,  # [R, H, P, N]
    tok_rows: jax.Array,  # [T]: each token's state row (>= R: none)
    fresh: jax.Array,  # [T] bool: the token is at position 0
) -> tuple[jax.Array, jax.Array]:
    """One token a sequence: (y [T, H, P] float32, the state with the
    live rows rewritten)."""
    r, h, p, n = state.shape
    g = b.shape[1]
    tok, held = _rows_of(tok_rows, r)
    f32 = jnp.float32
    x_r = _grouped(x[tok].astype(f32), g)  # [R, G, J, P]
    dt_r = dt[tok].reshape(r, g, h // g)
    b_r, c_r = b[tok].astype(f32), c[tok].astype(f32)  # [R, G, N]
    decay = jnp.exp(dt_r * a.reshape(g, h // g))
    old = jnp.where(
        fresh[tok][:, None, None, None, None],
        0.0,
        state.astype(f32).reshape(r, g, h // g, p, n),
    )
    new = (
        decay[..., None, None] * old
        + (dt_r[..., None] * x_r)[..., None] * b_r[:, :, None, None, :]
    )
    y_r = (new * c_r[:, :, None, None, :]).sum(-1)
    y_r = y_r + d_skip.reshape(g, h // g, 1) * x_r
    state = jnp.where(
        held[:, None, None, None],
        new.reshape(r, h, p, n).astype(state.dtype),
        state,
    )
    y = y_r.reshape(r, h, p)[jnp.minimum(tok_rows, r - 1)]
    return y, state


def ssd_chunk_scan(
    x: jax.Array,  # [T, H, P]
    dt: jax.Array,  # [T, H] float32, after the softplus
    a: jax.Array,  # [H] float32, negative
    b: jax.Array,  # [T, G, N]
    c: jax.Array,  # [T, G, N]
    d_skip: jax.Array,  # [H] float32
    state: jax.Array,  # [R, H, P, N]
    *,
    seq_ids: jax.Array,  # [T] sorted; padding = S
    positions: jax.Array,  # [T]
    chunk_starts: jax.Array,  # [S]
    slots: jax.Array,  # [S]: each sequence's state row (>= R: none)
    last_rows: jax.Array,  # [S]: flat index of its last token
    chunk: int,
    cumsum_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """A flat batch of ragged chunks: (y [T, H, P] float32, the state
    with the step's sequences' rows rewritten).  ``cumsum_dtype`` is
    float32; anything else is a test's control."""
    t, h, p = x.shape
    r, n = state.shape[0], state.shape[-1]
    g = b.shape[1]
    j = h // g
    s = slots.shape[0]
    ln = min(chunk, t)
    assert t % ln == 0, (t, chunk)
    k = t // ln
    f32, cd = jnp.float32, x.dtype

    def tiles(v):
        return v.reshape(k, ln, *v.shape[1:])

    sid = tiles(seq_ids)  # [K, L]
    cum = jnp.cumsum(
        tiles(dt * a).astype(cumsum_dtype), axis=1
    ).astype(f32)  # [K, L, H]
    # cum0[k, i] = Σ of the tile's first i tokens: the sum *before*
    # token i, and at i = L the whole tile's
    cum0 = jnp.pad(cum, ((0, 0), (1, 0), (0, 0)))
    end = cum[:, -1]  # [K, H]
    xdt = tiles((x.astype(f32) * dt[..., None]).astype(cd))  # [K,L,H,P]
    xdt_g = _grouped(xdt, g)  # [K, L, G, J, P]
    b_k, c_k = tiles(b), tiles(c)  # [K, L, G, N]
    last = sid[:, -1]  # [K] the sequence that runs to the tile's end
    prev_last = jnp.concatenate([jnp.full((1,), -1, last.dtype), last[:-1]])

    # ---- within a tile: (C Bᵀ ⊙ L) · (Δ x) --------------------------
    same = (sid[:, :, None] == sid[:, None, :]) & jnp.tril(
        jnp.ones((ln, ln), bool)
    )
    decay = jnp.exp(
        jnp.where(
            same[..., None], cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf
        )
    )  # [K, L, L, H]
    scores = jnp.einsum(
        "ktgn,ksgn->ktsg", c_k, b_k, preferred_element_type=f32
    )
    m = (decay.reshape(k, ln, ln, g, j) * scores[..., None]).astype(cd)
    y = jnp.einsum(
        "ktsgj,ksgjp->ktgjp", m, xdt_g, preferred_element_type=f32
    )

    # ---- each sequence, by the state row it holds -------------------
    seq, held = _rows_of(slots, r)  # [R]
    # every sequence's first token of the step, from its last [S]
    first_tok = jnp.clip(
        last_rows - (positions[last_rows] - chunk_starts), 0, t - 1
    )
    e_tok, f_tok = last_rows[seq], first_tok[seq]
    carried = held & (chunk_starts[seq] > 0)  # it reads its row
    k0, i0 = f_tok // ln, f_tok % ln
    k1, i1 = e_tok // ln, e_tok % ln
    tile_ids = jnp.arange(k)
    first_tile = k0[:, None] == tile_ids  # [R, K]
    last_tile = k1[:, None] == tile_ids

    # A row's tile of a per-tile array, as a one-hot sum: exact (one
    # term is not zero), and no gather of whole tiles, which XLA walks
    # slice by slice.
    def pick(onehot, v):
        if jnp.issubdtype(v.dtype, jnp.floating):
            return jnp.einsum(
                "rk,k...->r...", onehot.astype(v.dtype), v,
                precision=HIGHEST if v.dtype == f32 else None,
                preferred_element_type=f32,
            ).astype(v.dtype)
        w = onehot.reshape(r, k, *([1] * (v.ndim - 1)))
        return jnp.where(w, v[None], jnp.zeros((), v.dtype)).sum(1)

    def at(v, i):  # v [R, L', ...] at index i [R] of axis 1, likewise
        w = (i[:, None] == jnp.arange(v.shape[1])).reshape(
            r, v.shape[1], *([1] * (v.ndim - 2))
        )
        return jnp.where(w, v, jnp.zeros((), v.dtype)).sum(1)

    cum_first, cum_last = pick(first_tile, cum), pick(last_tile, cum)
    before = at(pick(first_tile, cum0), i0)  # [R, H]: the sum before it
    s_g = state.reshape(r, g, j, p, n)
    # its stored state's part of y, over the tile its chunk starts in
    # (a weight of zero where the row is not read: a row only ever
    # holds finite numbers)
    in_first = (pick(first_tile, sid) == seq[:, None]) & carried[:, None]
    w_first = jnp.where(
        in_first[..., None], jnp.exp(cum_first - before[:, None, :]), 0.0
    )  # [R, L, H]
    y_init = (
        jnp.einsum(
            "rtgn,rgjpn->rtgjp", pick(first_tile, c_k), s_g.astype(cd),
            preferred_element_type=f32,
        )
        * w_first.reshape(r, ln, g, j)[..., None]
    ).astype(cd)
    y = y + jnp.einsum(
        "rk,rtgjp->ktgjp", first_tile.astype(cd), y_init,
        preferred_element_type=f32,
    )

    # ---- between tiles: the state that crosses each tile's edge -----
    # what a tile's own tokens add to its last sequence's state ...
    w_end = jnp.where(
        (sid == last[:, None])[..., None], jnp.exp(end[:, None, :] - cum), 0.0
    )  # [K, L, H]
    beta = jnp.einsum(
        "ksgjp,ksgn->kgjpn",
        (xdt_g * w_end.reshape(k, ln, g, j)[..., None]).astype(cd),
        b_k,
        preferred_element_type=f32,
    )
    # ... and, where that sequence's chunk starts in the tile, what its
    # stored state adds
    ls = jnp.minimum(last, s - 1)
    l_first = first_tok[ls]
    starts_here = (
        (last < s) & (l_first // ln == tile_ids) & (chunk_starts[ls] > 0)
    )
    w_s0 = jnp.where(
        starts_here[:, None],
        jnp.exp(end - cum0[tile_ids, l_first % ln]),
        0.0,
    )  # [K, H]
    of_last = (slots[ls][:, None] == jnp.arange(r)) & starts_here[:, None]
    beta = beta + w_s0.reshape(k, g, j, 1, 1) * jnp.einsum(
        "kr,rgjpn->kgjpn", of_last.astype(state.dtype), s_g,
        preferred_element_type=f32,
    )
    # the recurrence over tiles, unrolled: a tile passes the incoming
    # state on only where it is all one sequence with the tile before
    whole = jnp.where((last == prev_last)[:, None], jnp.exp(end), 0.0)
    run = jnp.zeros((g, j, p, n), f32)
    incoming = []
    for i in range(k):
        incoming.append(run)
        run = whole[i].reshape(g, j, 1, 1) * run + beta[i]
    r_in = jnp.stack(incoming)  # [K, G, J, P, N]
    uses = (sid == prev_last[:, None])[..., None]  # [K, L, 1]
    w_in = jnp.where(uses, jnp.exp(cum), 0.0)  # [K, L, H]
    y = y + jnp.einsum(
        "ktgn,kgjpn->ktgjp", c_k, r_in.astype(cd), preferred_element_type=f32
    ) * w_in.reshape(k, ln, g, j)[..., None]

    # ---- what each sequence leaves behind ---------------------------
    at_end = at(cum_last, i1)  # [R, H]: the tile's sum through its last token
    w_last = jnp.where(
        (pick(last_tile, sid) == seq[:, None])[..., None],
        jnp.exp(at_end[:, None, :] - cum_last),
        0.0,
    )  # [R, L, H]
    final = jnp.einsum(
        "rtgjp,rtgn->rgjpn",
        (pick(last_tile, xdt_g)
         * w_last.reshape(r, ln, g, j)[..., None]).astype(cd),
        pick(last_tile, b_k),
        preferred_element_type=f32,
    )
    final = final + jnp.where(
        (carried & (k0 == k1))[:, None], jnp.exp(at_end - before), 0.0
    ).reshape(r, g, j, 1, 1) * s_g.astype(f32)
    # the one sequence that came in over a tile's edge and ends in the
    # tile: what came in, decayed through its last token, onto its row
    cs = jnp.clip(prev_last, 0, s - 1)
    c_end = last_rows[cs]
    ends_here = (prev_last >= 0) & (prev_last < s) & (c_end // ln == tile_ids)
    w_cross = jnp.where(
        ends_here[:, None], jnp.exp(cum[tile_ids, c_end % ln]), 0.0
    )  # [K, H]
    final = final.at[jnp.where(ends_here, slots[cs], r)].add(
        w_cross.reshape(k, g, j, 1, 1) * r_in, mode="drop"
    )
    state = jnp.where(
        held[:, None, None, None],
        final.reshape(r, h, p, n).astype(state.dtype),
        state,
    )
    y = y.reshape(t, h, p) + d_skip[:, None] * x.astype(f32)
    return y, state


def ssm_recurrence(x, dt, a, b, c, d_skip, state):
    """The recurrence itself, token by token over ONE sequence (tests:
    what both forms above must equal).  x [T, H, P], dt [T, H], b, c
    [T, G, N], state [H, P, N] float32 → (y [T, H, P], the state after
    the last token)."""
    h = x.shape[1]
    g = b.shape[1]

    def step(st, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, h // g, axis=0)  # [H, N]
        c_h = jnp.repeat(c_t, h // g, axis=0)
        st = (
            jnp.exp(dt_t * a)[:, None, None] * st
            + (dt_t[:, None] * x_t)[..., None] * b_h[:, None, :]
        )
        y_t = (st * c_h[:, None, :]).sum(-1) + d_skip[:, None] * x_t
        return st, y_t

    state, y = jax.lax.scan(step, state, (x, dt, b, c))
    return y, state
