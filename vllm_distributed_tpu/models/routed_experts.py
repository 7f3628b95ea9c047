"""The routed layer's second half, shared by every model that has one:
given each token's expert indices and weights (the model's own router:
Mixtral's softmax, LFM2's sigmoid with a choice-only bias), run the
experts ``w2(silu(w1 x) * w3 x)`` and combine.  Three forms, chosen a
program by ``select_moe_impl`` from the routed rows of the step being
traced and from what the expert tensor says of itself:

- ``dense``: every expert on every token, a [T, E] combine matrix
  (zeros outside the top-k).  Exact and GSPMD-friendly; what a
  weight-bandwidth-bound step (the fused decode scan) runs, and the
  oracle of the other two.
- ``ragged``: the T×k assignments flattened and sorted by expert, one
  grouped matmul a projection (``jax.lax.ragged_dot``, which the TPU
  compiler lowers to a grouped kernel of its own: no loop, work in
  proportion to the rows routed), the weighted rows scatter-added back.
- ``grouped``: the same sorted dispatch on one chip through
  ``ops/pallas/grouped_matmul``: over int8 experts it streams the int8
  tiles (no bf16 copy of the stack); over unquantized experts of a
  model that asks for it (``unquantized_backend``) the tiles go to the
  MXU as they are.
- ``branch`` (the fourth form, ISSUE 45): the dense form's arithmetic
  one expert at a time, each behind a ``jax.lax.cond`` on "some live
  row picked it", so a weight-bandwidth-bound step reads only the
  experts its rows reach.  Unquantized experts off a mesh, in a step
  so small beside the router's width that an even router is expected
  to leave an expert of the stack unreached (``BRANCH_MIN_UNREACHED``).

Token drop/capacity factors are never used.  Rows outside ``live`` (a
step's padding) join no expert's group in the sorted forms.

**A pick may belong to no expert held here.**  The chip may hold E of a
wider router's experts (one chip of an expert-parallel group, outside a
mesh: models/deepseek_v3.py); its model hands over indices local to
the held stack with every pick of an expert held elsewhere at ``E`` or
above (``local_expert_ids``).  Such a pick has no column in the dense
form's combine matrix, joins no group in the sorted forms and adds
nothing; its weight is never read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_distributed_tpu.models.common import (
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_ROUTER,
)

# Routed rows (tokens × top_k) above which int8 experts on one chip run
# the grouped kernel instead of the dense einsum.  Below it the step is
# bound by reading the experts' weights, which both do once, and the
# grouped path pads every expert to a 128-row tile: nothing to win.
# Fixed from a one-layer sweep on the v5e at Mixtral's widths (PERF.md,
# PR 27).
GROUPED_MIN_ROWS = 256
# Experts of the stack that an even router is expected to leave with no
# row, ``E * (1 - top_k / router_width) ** tokens``, from which a step
# that would run the dense einsum runs the ``branch`` form instead.
# The einsum reads all E experts whatever the picks; a conditional an
# expert reads the reached ones, three products of one expert at a
# time: alone on the chip those read at 82% of HBM's peak against the
# einsum's 88% (break-even at 1.2 of 16 unreached), inside the GigaChat
# cell's fused scan at the einsum's own rate (PERF.md, PR 45), so there
# is something to win from about one expert skipped.  At 16 of a
# 256-wide router's experts and 8 picks a row: 64 rows leave 2.1
# (branch), 128 rows 0.27 (dense).
BRANCH_MIN_UNREACHED = 1.0


def select_moe_impl(
    requested: str, rows: int, w1, on_mesh: bool,
    unquantized_backend: str | None = None,
    *, tokens: int | None = None, top_k: int | None = None,
    router_width: int | None = None,
) -> str:
    """"dense" | "ragged" | "grouped" | "branch" for one expert layer, from
    VDT_MOE_IMPL (``requested``), the routed rows of the step being
    traced and the expert tensor itself.

    ``auto``: int8 experts stamped for the Pallas backend, on one chip,
    in a step of more than GROUPED_MIN_ROWS routed rows (prefill, a
    mixed step, a spec-verify step) take the grouped int8 kernel: such
    a step is compute-bound and the dense einsum spends E/k times the
    model's FLOPs there (step_experts_ms 53.97 -> 16.70, ledger, PR 27).
    Every other quantized case stays dense: decode is
    weight-BANDWIDTH-bound, where XLA fuses the dequant into the einsum
    so the compressed bytes stream once (decode_experts_roofline_pct
    92.6, ledger, PR 30), and ``ragged_dot`` cannot fuse a producer: it
    materializes the bf16 expert stack per call (pre-round; its cost is
    not measured on this tree) — a statement about ragged_dot, not about
    routing.  Unquantized experts route through ragged_dot on big row
    counts, unless the model names the kernel backend its unquantized
    experts may use (``unquantized_backend``: "pallas" |
    "pallas_interpret"): then they take ``grouped_matmul`` where it has
    tiles, because the TPU's ``ragged_dot`` cost 4.9 x a dense FFN of
    the same rows at 32 experts of 1,792 (PERF.md, PR 38).  ``ragged``
    on a tensor the grouped kernel takes means that kernel; ``dense`` is
    always the dense einsum.

    A model that says what its router does (``tokens``: the step's
    padded rows, ``top_k``: the picks a row makes, ``router_width``: the
    experts it picks over, which may be more than the stack holds) gets
    ``branch`` for unquantized experts off a mesh where ``auto`` would
    run the dense einsum and an even router is expected to leave
    BRANCH_MIN_UNREACHED experts of the stack with no row; without the
    three the answer is what it was.  ``branch`` asked for by name is
    the dense einsum over quantized experts (its dequant fuses there)
    and on a mesh."""
    from vllm_distributed_tpu.ops.pallas import grouped_matmul as gm
    from vllm_distributed_tpu.ops.quant import QuantizedTensor

    quantized = isinstance(w1, QuantizedTensor)
    if quantized:
        grouped_ok = (
            w1.bits == 8
            and w1.matmul in ("pallas", "pallas_interpret")
            and not on_mesh
            and w1.mesh is None
            and (
                w1.matmul == "pallas_interpret"
                or gm.has_tiles(w1.shape[-2], w1.shape[-1])
            )
        )
    else:
        grouped_ok = (
            unquantized_backend in ("pallas", "pallas_interpret")
            and not on_mesh
            and (
                unquantized_backend == "pallas_interpret"
                or gm.has_unquantized_tiles(
                    w1.shape[-2], w1.shape[-1], w1.dtype.itemsize
                )
            )
        )
    if requested == "auto":
        if grouped_ok and rows > GROUPED_MIN_ROWS:
            return "grouped"
        impl = "dense" if (quantized or rows <= 256) else "ragged"
        if (
            impl == "dense"
            and not (quantized or on_mesh)
            and None not in (tokens, top_k, router_width)
            and w1.shape[0] * (1.0 - top_k / router_width) ** tokens
            >= BRANCH_MIN_UNREACHED
        ):
            return "branch"
        return impl
    if requested == "ragged" and grouped_ok:
        return "grouped"
    if requested == "branch" and (quantized or on_mesh):
        return "dense"
    return requested


class RoutedLayerCounts:
    """What a model with ``moe_impl`` says of a step it is handed
    (``ops.attention.StepShape``): the form its routed layers were
    traced with, from the first of them (``first_routed_layer``: a model
    with leading dense layers says where they start) and the rows a
    layer sees."""

    first_routed_layer = 0

    def step_counts(self, shape, params) -> tuple[dict, dict]:
        """(the ``runner.dispatch`` span's ``moe``, one grouped step
        more on the worker's counter where it says so)."""
        impl = self.moe_impl(
            params["layers"][self.first_routed_layer], shape.rows_pad
        )
        grouped = {"moe_grouped_steps": {"": 1}} if impl == "grouped" else {}
        return {"moe": impl}, grouped


def local_expert_ids(topi, held_lo: int, num_held: int):
    """A wider router's indices as the held stack's: expert ``held_lo +
    j`` becomes ``j``, every expert held elsewhere ``num_held`` (no
    expert here)."""
    local = topi - held_lo
    return jnp.where(
        (local >= 0) & (local < num_held), local, num_held
    ).astype(jnp.int32)


def experts_dense(h, topi, topw, w1, w3, w2):
    """Every expert on every token; h [T, H], topi/topw [T, k].  An
    index of E or above has no column: the scatter drops it."""
    from vllm_distributed_tpu.ops.quant import maybe_dequantize

    t = h.shape[0]
    with jax.named_scope(SCOPE_MOE_ROUTER):
        combine = (
            jnp.zeros((t, w1.shape[0]), jnp.float32)
            .at[jnp.arange(t)[:, None], topi]
            .set(topw, mode="drop")
            .astype(h.dtype)
        )
    with jax.named_scope(SCOPE_MOE_EXPERTS):
        w1 = maybe_dequantize(w1, h.dtype)
        w3 = maybe_dequantize(w3, h.dtype)
        w2 = maybe_dequantize(w2, h.dtype)
        h1 = jnp.einsum("th,ehi->tei", h, w1)
        h3 = jnp.einsum("th,ehi->tei", h, w3)
        inner = jax.nn.silu(h1) * h3
        return jnp.einsum("tei,eih,te->th", inner, w2, combine)


def picks_by_expert(topi, num_experts: int, live=None):
    """(picked [T, k, E] bool: pick ``j`` of row ``t`` is of expert
    ``e`` of the stack; counts [E] int32: the picks of each expert).
    Rows outside ``live`` and indices of E or above (experts held
    elsewhere) pick no expert of the stack."""
    picked = topi[..., None] == jnp.arange(num_experts, dtype=topi.dtype)
    if live is not None:
        picked = picked & live[:, None, None]
    return picked, picked.sum(axis=(0, 1), dtype=jnp.int32)


def experts_branch(h, topi, topw, w1, w3, w2, live=None):
    """The dense form one expert at a time, each behind a conditional:
    expert ``e`` runs over all T rows, weighted by its column of the
    combine matrix (zeros for the rows that did not pick it), only
    where some live row picked it; an expert no row reached is not
    read.  No gather buffer, no capacity, no drop.  Padding rows come
    back as zeros.  Python's loop over the stack makes E conditionals
    and no XLA loop (a single step must hold none)."""
    e = w1.shape[0]
    with jax.named_scope(SCOPE_MOE_ROUTER):
        picked, counts = picks_by_expert(topi, e, live)
        combine = jnp.where(
            picked, topw.astype(jnp.float32)[..., None], 0.0
        ).sum(axis=1)  # [T, E]
        reached = counts > 0
    with jax.named_scope(SCOPE_MOE_EXPERTS):
        y = jnp.zeros(h.shape, jnp.float32)
        for j in range(e):
            def run(y, j=j):
                inner = jax.nn.silu(h @ w1[j]) * (h @ w3[j])
                out = jnp.dot(
                    inner, w2[j], preferred_element_type=jnp.float32
                )
                return y + out * combine[:, j, None]

            y = jax.lax.cond(reached[j], run, lambda y: y, y)
        return y.astype(h.dtype)


def sorted_dispatch(topi, num_experts: int, live=None):
    """The sorted forms' shared first half: flatten the T×k assignments
    and sort them by expert.  Returns (flat expert ids [T*k], order
    [T*k]: sorted row -> flat assignment, group sizes [E]).  Rows of
    tokens outside ``live`` (a step's padding) take the id E, which
    picks of experts held elsewhere come with: they sort last and join
    no group."""
    e, k = num_experts, topi.shape[1]
    flat_e = topi.reshape(-1).astype(jnp.int32)  # [T*k]
    if live is not None:
        flat_e = jnp.where(jnp.repeat(live, k), flat_e, e)
    order = jnp.argsort(flat_e)
    gs = (flat_e[:, None] == jnp.arange(e, dtype=jnp.int32)).sum(
        axis=0, dtype=jnp.int32
    )
    return flat_e, order, gs


def experts_ragged(
    h, topi, topw, w1, w3, w2, live=None, mesh=None, expert_parallel=False
):
    """Sorted ragged dispatch: ONE grouped matmul per projection
    (jax.lax.ragged_dot) over the rows sorted by expert, the weighted
    results scatter-added back — ~k/E of the dense form's expert FLOPs.

    Sharding: under EP each device holds E/tp whole experts; the
    activations are replicated over "tp", so instead of an all-to-all
    each shard runs the grouped matmul over the FULL sorted row range
    with its local expert stack — rows outside its experts' range fold
    into the edge groups (so group offsets stay aligned without
    padding/copying weights) and are masked from the psum-combined
    output.  Without EP, experts split over their intermediate dim like
    the dense MLP (partial products psum)."""
    from vllm_distributed_tpu.ops.quant import maybe_dequantize

    t, k = topi.shape
    with jax.named_scope(SCOPE_MOE_ROUTER):
        flat_e, order, gs = sorted_dispatch(topi, w1.shape[0], live)
        tok = order // k
        xs = h[tok]  # [T*k, H] sorted by expert
        row_w = topw.reshape(-1)[order].astype(h.dtype)  # [T*k]

    with jax.named_scope(SCOPE_MOE_EXPERTS):
        w1 = maybe_dequantize(w1, h.dtype)
        w3 = maybe_dequantize(w3, h.dtype)
        w2 = maybe_dequantize(w2, h.dtype)
        tp = mesh.shape.get("tp", 1) if mesh is not None else 1
        if tp > 1 and expert_parallel:
            orows = _ragged_ep(xs, gs, w1, w3, w2, mesh, tp)
        elif tp > 1:
            orows = _ragged_tp(xs, gs, w1, w3, w2, mesh)
        else:
            h1 = jax.lax.ragged_dot(xs, w1, gs)
            h3 = jax.lax.ragged_dot(xs, w3, gs)
            inner = jax.nn.silu(h1) * h3
            orows = jax.lax.ragged_dot(inner, w2, gs)

    # f32 accumulation for the k-way combine: the dense oracle's
    # einsum promotes the combine matrix to f32, so a bf16
    # scatter-add here would drift from it (ADVICE r4 #1).
    y = jnp.zeros((t, h.shape[1]), jnp.float32)
    contrib = orows.astype(jnp.float32) * row_w.astype(jnp.float32)[:, None]
    # rows past the last group (padding; picks of experts held
    # elsewhere) belong to no expert: whatever the grouped matmul left
    # there is not a product
    contrib = jnp.where((flat_e[order] < w1.shape[0])[:, None], contrib, 0.0)
    return y.at[tok].add(contrib).astype(h.dtype)


def experts_grouped(
    h, topi, topw, w1, w3, w2, live=None, interpret: bool = False
):
    """The sorted dispatch on one chip: the rows go through
    ops/pallas/grouped_matmul — over int8 experts the kernel that
    streams the int8 tiles themselves (no bf16 copy of the stack, which
    is what ``ragged_dot`` would need; whether it is interpreted is on
    the tensor's stamp), over unquantized ones the kernel that feeds the
    tiles to the MXU as they are (``interpret`` says how it runs).  Each
    expert's group is padded to whole row tiles, so
    the gather writes that layout directly and the combine gathers back
    out of it (a token's k rows, weighted and summed in f32 like the
    other forms) instead of scatter-adding.  Tokens outside ``live``
    join no group and come back as zeros; a pick of an expert held
    elsewhere joins none and adds nothing."""
    from vllm_distributed_tpu.ops.pallas import grouped_matmul as gm
    from vllm_distributed_tpu.ops.quant import QuantizedTensor

    t, k = topi.shape
    e = w1.shape[0]
    m = t * k
    quantized = isinstance(w1, QuantizedTensor)
    if quantized:
        interpret = w1.matmul == "pallas_interpret"
    mp = gm.padded_rows(m, e, gm.BLOCK_ROWS)
    with jax.named_scope(SCOPE_MOE_ROUTER):
        flat_e, order, gs = sorted_dispatch(topi, e, live)
        starts, tile_expert, _ = gm.group_layout(gs, mp, gm.BLOCK_ROWS)
        # sorted row r of expert e sits at padded row r + shift[e]
        shift = starts - (jnp.cumsum(gs) - gs)
        row_e = jnp.repeat(tile_expert, gm.BLOCK_ROWS)
        src = jnp.arange(mp, dtype=jnp.int32) - shift[row_e]
        xs = h[order[jnp.clip(src, 0, m - 1)] // k]  # [MP, H]
        # where each assignment's row went: its rank among the
        # sorted rows, moved into its expert's padded range
        rank = jnp.argsort(order).astype(jnp.int32)
        pos = rank + shift[jnp.minimum(flat_e, e - 1)]
        pos = jnp.minimum(pos, mp - 1).reshape(t, k)

    with jax.named_scope(SCOPE_MOE_EXPERTS):
        if quantized:
            inner = gm.grouped_int8_matmul(
                xs, w1.q, w1.scale, gs, q_up=w3.q, scale_up=w3.scale,
                interpret=interpret,
            )
            orows = gm.grouped_int8_matmul(
                inner, w2.q, w2.scale, gs, interpret=interpret
            )
        else:
            inner = gm.grouped_matmul(
                xs, w1, gs, w_up=w3, interpret=interpret
            )
            orows = gm.grouped_matmul(inner, w2, gs, interpret=interpret)

    w = topw.astype(h.dtype).astype(jnp.float32)[..., None]
    # a pick outside every group (a padding token's; an expert held
    # elsewhere) points at a row that is no product of its own
    in_group = (flat_e < e).reshape(t, k, 1)
    y = jnp.where(in_group, orows[pos].astype(jnp.float32) * w, 0.0)
    return y.sum(axis=1).astype(h.dtype)


def routed_experts(
    impl: str, h, topi, topw, w1, w3, w2, live=None, mesh=None,
    expert_parallel=False, interpret: bool = False,
):
    """The experts' part of one routed layer in the form ``impl``
    (``select_moe_impl``'s word for this program)."""
    if impl == "dense":
        return experts_dense(h, topi, topw, w1, w3, w2)
    if impl == "grouped":
        return experts_grouped(h, topi, topw, w1, w3, w2, live, interpret)
    if impl == "branch":
        return experts_branch(h, topi, topw, w1, w3, w2, live)
    return experts_ragged(
        h, topi, topw, w1, w3, w2, live, mesh, expert_parallel
    )


def _ragged_ep(xs, gs, w1, w3, w2, mesh, tp):
    """EP shard_map: each device's local experts own a contiguous
    range of the sorted rows; out-of-range rows are folded into the
    first/last local group (keeping offsets aligned without weight
    copies), computed as garbage, masked, and psum-combined."""
    from jax.sharding import PartitionSpec as P

    e_local = w1.shape[0] // tp
    m = xs.shape[0]

    def body(xs_, gs_, w1_, w3_, w2_):
        idx = jax.lax.axis_index("tp")
        cum = jnp.cumsum(gs_)
        lo_e = idx * e_local
        start = jnp.where(lo_e > 0, cum[jnp.maximum(lo_e - 1, 0)], 0)
        end = cum[lo_e + e_local - 1]
        gs_local = jax.lax.dynamic_slice(gs_, (lo_e,), (e_local,))
        # Fold the out-of-range rows into the edge groups.
        gs_fold = gs_local.at[0].add(start)
        gs_fold = gs_fold.at[e_local - 1].add(m - end)
        h1 = jax.lax.ragged_dot(xs_, w1_, gs_fold)
        h3 = jax.lax.ragged_dot(xs_, w3_, gs_fold)
        inner = jax.nn.silu(h1) * h3
        orows = jax.lax.ragged_dot(inner, w2_, gs_fold)
        rows = jnp.arange(m, dtype=jnp.int32)
        in_range = (rows >= start) & (rows < end)
        orows = jnp.where(in_range[:, None], orows, 0)
        return jax.lax.psum(orows, "tp")

    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(), P(),
            P("tp", None, None), P("tp", None, None),
            P("tp", None, None),
        ),
        out_specs=P(),
        check_vma=False,
    )
    return f(xs, gs, w1, w3, w2)


def _ragged_tp(xs, gs, w1, w3, w2, mesh):
    """Non-EP tp: experts split over the intermediate dim (like the
    dense MLP); w2's partial products psum inside the region."""
    from jax.sharding import PartitionSpec as P

    def body(xs_, gs_, w1_, w3_, w2_):
        h1 = jax.lax.ragged_dot(xs_, w1_, gs_)
        h3 = jax.lax.ragged_dot(xs_, w3_, gs_)
        inner = jax.nn.silu(h1) * h3
        part = jax.lax.ragged_dot(inner, w2_, gs_)
        return jax.lax.psum(part, "tp")

    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(), P(),
            P(None, None, "tp"), P(None, None, "tp"),
            P(None, "tp", None),
        ),
        out_specs=P(),
        check_vma=False,
    )
    return f(xs, gs, w1, w3, w2)
