"""The routed layer every model shares (models/routed_experts.py) when
a pick may belong to no expert held here (ISSUE 43): indices of E or
above have no column in the dense form's combine matrix, join no group
in the sorted forms and add nothing, in all three forms against the
dense oracle over the whole router; with every pick held the forms are
what they were.  The fourth form, ``branch`` (ISSUE 45: an expert
behind a conditional), is a case of each, and runs the branches of the
reached experts alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_distributed_tpu.models import routed_experts as rx

T, H, IM, WIDTH, K = 24, 32, 16, 12, 3


def _layer(seed=0, dtype=jnp.float32):
    """A router's picks over WIDTH experts with weights, and the whole
    expert stack."""
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((T, H)), dtype)
    topi = jnp.asarray(np.stack(
        [rng.permutation(WIDTH)[:K] for _ in range(T)]), jnp.int32)
    topw = jnp.asarray(rng.random((T, K)) + 0.1, jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((WIDTH, H, IM)) * 0.2, dtype)
              for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((WIDTH, IM, H)) * 0.2, dtype)
    return h, topi, topw, w1, w3, w2


def _forms(h, local, topw, w1, w3, w2, live):
    """The four forms over one held stack (the grouped kernel
    interpreted)."""
    return {
        "dense": rx.experts_dense(h, local, topw, w1, w3, w2),
        "ragged": rx.experts_ragged(h, local, topw, w1, w3, w2, live),
        "grouped": rx.experts_grouped(
            h, local, topw, w1, w3, w2, live, interpret=True),
        "branch": rx.experts_branch(h, local, topw, w1, w3, w2, live),
    }


FORMS = ["dense", "ragged", "grouped", "branch"]


def test_local_ids_send_every_absent_pick_past_the_stack():
    topi = jnp.asarray([[0, 3, 4], [7, 8, 11], [5, 6, 2]], jnp.int32)
    got = np.asarray(rx.local_expert_ids(topi, 4, 4))
    assert got.tolist() == [[4, 4, 0], [3, 4, 4], [1, 2, 4]]
    assert got.dtype == np.int32
    # rank 0 keeps its ids; nothing goes negative (an index of -1 would
    # wrap to the last held expert in a scatter)
    assert np.asarray(rx.local_expert_ids(topi, 0, 4)).min() == 0


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("lo,held", [(0, 4), (4, 4), (8, 4), (2, 7)])
def test_a_share_computes_its_own_experts_part_and_nothing_else(
        form, lo, held):
    """Held experts lo .. lo + held of a 12-wide router: each form over
    the held stack with local indices equals the dense oracle over the
    whole stack with every weight of an absent pick set to zero."""
    h, topi, topw, w1, w3, w2 = _layer()
    is_held = (topi >= lo) & (topi < lo + held)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(rx.experts_dense(
            h, topi, jnp.where(is_held, topw, 0.0), w1, w3, w2))
        local = rx.local_expert_ids(topi, lo, held)
        stack = (w[lo:lo + held] for w in (w1, w3, w2))
        got = np.asarray(_forms(
            h, local, topw, *stack, jnp.ones(T, bool))[form])
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-5
    assert 0 < int(is_held.sum()) < T * K  # some held, some absent


@pytest.mark.parametrize("form", FORMS)
def test_the_shares_add_up_to_the_whole_layer(form):
    h, topi, topw, w1, w3, w2 = _layer(1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(rx.experts_dense(h, topi, topw, w1, w3, w2))
        total = np.zeros_like(want)
        for lo in range(0, WIDTH, 4):
            local = rx.local_expert_ids(topi, lo, 4)
            stack = (w[lo:lo + 4] for w in (w1, w3, w2))
            total += np.asarray(_forms(
                h, local, topw, *stack, jnp.ones(T, bool))[form])
    assert np.abs(total - want).max() < 1e-5


@pytest.mark.parametrize("form", FORMS[1:])
def test_a_step_in_which_no_pick_is_held_adds_nothing(form):
    """Every pick of every row held elsewhere: all groups are empty,
    the grouped layout holds no live tile, and the result is zeros, not
    whatever the kernel left in its output."""
    h, topi, topw, w1, w3, w2 = _layer(2)
    local = jnp.full((T, K), 4, jnp.int32)
    got = np.asarray(_forms(
        h, local, topw, w1[:4], w3[:4], w2[:4], jnp.ones(T, bool))[form])
    assert not got.any()
    _, _, gs = rx.sorted_dispatch(local, 4, jnp.ones(T, bool))
    assert np.asarray(gs).tolist() == [0, 0, 0, 0]
    assert not np.asarray(rx.picks_by_expert(local, 4)[1]).any()


def _poisoned(w, experts):
    """The stack with every value of ``experts`` NaN: a product with
    one of them poisons every row it is added to, whatever its weight
    (0 x NaN is NaN), so a finite result says they were never read."""
    return w.at[jnp.asarray(experts)].set(jnp.nan)


def test_a_step_in_which_no_pick_is_held_runs_no_branch():
    h, topi, topw, w1, w3, w2 = _layer(2)
    local = jnp.full((T, K), 4, jnp.int32)
    stack = [_poisoned(w[:4], range(4)) for w in (w1, w3, w2)]
    got = jax.jit(rx.experts_branch)(h, local, topw, *stack)
    assert not np.asarray(got).any()  # zeros, and no NaN among them
    # the dense form reads every expert whatever the picks
    assert np.isnan(np.asarray(
        rx.experts_dense(h, local, topw, *stack))).all()


def test_branch_skips_exactly_the_experts_no_live_row_picked():
    """A hand-made ``topi`` over a stack of 6 (index 6: held
    elsewhere): live rows pick experts 0, 2 and 5; expert 3 is picked
    by a padding row alone, 1 and 4 by nobody.  Three branches run,
    three are skipped: the count a model puts on ``skipped``
    (``picks_by_expert``'s zeros) and, by poisoned weights, the
    branches themselves."""
    topi = jnp.asarray(
        [[0, 2, 6], [2, 6, 6], [5, 0, 6], [6, 6, 6], [3, 6, 6]], jnp.int32)
    live = jnp.asarray([True, True, True, True, False])
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.standard_normal((5, H)), jnp.float32)
    topw = jnp.asarray(rng.random((5, K)) + 0.1, jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((6, H, IM)) * 0.2)
              for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((6, IM, H)) * 0.2)
    picked, counts = rx.picks_by_expert(topi, 6, live)
    assert np.asarray(counts).tolist() == [2, 0, 2, 0, 0, 1]
    assert int((counts == 0).sum()) == 3  # skipped
    assert picked.shape == (5, K, 6) and int(picked.sum()) == 5
    # without ``live`` the padding row's pick reaches expert 3
    assert np.asarray(rx.picks_by_expert(topi, 6)[1]).tolist() == [
        2, 0, 2, 1, 0, 1]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(rx.experts_dense(
            h, topi, jnp.where(live[:, None], topw, 0.0), w1, w3, w2))
        skipped = [1, 3, 4]
        got = np.asarray(jax.jit(rx.experts_branch)(
            h, topi, topw, *(_poisoned(w, skipped) for w in (w1, w3, w2)),
            live))
        # one reached expert poisoned: its branch runs
        bad = np.asarray(jax.jit(rx.experts_branch)(
            h, topi, topw, _poisoned(w1, [5]), w3, w2, live))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-5
    assert not got[3:].any()  # a row with no held pick; a padding row
    assert np.isnan(bad).any()


@pytest.mark.parametrize("form", FORMS[1:])
def test_padding_rows_and_absent_picks_join_no_group_together(form):
    """Padding rows (outside ``live``) and absent picks both carry the
    id E: the group sizes count held picks of live rows only, and the
    padding rows come back as zeros."""
    h, topi, topw, w1, w3, w2 = _layer(3)
    live = jnp.arange(T) < 17
    lo, held = 4, 4
    local = rx.local_expert_ids(topi, lo, held)
    _, _, gs = rx.sorted_dispatch(local, held, live)
    counted = [int(((topi[:17] == lo + e)).sum()) for e in range(held)]
    assert np.asarray(gs).tolist() == counted
    # what the branch form reaches its experts by
    assert np.asarray(rx.picks_by_expert(local, held, live)[1]).tolist() == (
        counted)
    with jax.default_matmul_precision("highest"):
        is_held = (topi >= lo) & (topi < lo + held) & live[:, None]
        want = np.asarray(rx.experts_dense(
            h, topi, jnp.where(is_held, topw, 0.0), w1, w3, w2))
        stack = (w[lo:lo + held] for w in (w1, w3, w2))
        got = np.asarray(_forms(h, local, topw, *stack, live)[form])
    assert not got[17:].any()
    assert np.abs(got - want).max() < 1e-5


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("with_live", [False, True])
def test_with_every_pick_held_the_forms_are_what_they_were(form, with_live):
    """Mixtral's and LFM2's case: indices over the whole stack, with
    and without ``live``: the three forms agree with the oracle."""
    h, topi, topw, w1, w3, w2 = _layer(4)
    live = jnp.arange(T) < 20 if with_live else None
    with jax.default_matmul_precision("highest"):
        want = np.asarray(rx.experts_dense(h, topi, topw, w1, w3, w2))
        got = np.asarray(_forms(h, topi, topw, w1, w3, w2, live)[form])
    rows = slice(0, 20) if with_live else slice(None)
    assert np.abs(got[rows] - want[rows]).max() < 1e-5


@pytest.mark.parametrize("form", ["grouped", "branch"])
def test_bfloat16_rows_with_absent_picks_against_the_dense_form(form):
    """The cells' dtype: bf16 rows and experts through the interpreted
    ``vdt_grouped_matmul`` and through the conditionals, absent picks
    among them, against the dense form in bf16 (each rounds every
    product; they differ by rounding)."""
    h, topi, topw, w1, w3, w2 = _layer(5, jnp.bfloat16)
    local = rx.local_expert_ids(topi, 4, 4)
    live = jnp.ones(T, bool)
    stack = [w[4:8] for w in (w1, w3, w2)]
    got = _forms(h, local, topw, *stack, live)
    assert got[form].dtype == jnp.bfloat16
    a, b = (np.asarray(got[f], np.float32) for f in (form, "dense"))
    assert np.abs(a - b).max() < 0.05 * np.abs(b).max()


def test_bfloat16_branch_with_every_pick_held_is_the_dense_form():
    """Mixtral's and LFM2's case in the cells' dtype: indices over the
    whole stack."""
    h, topi, topw, w1, w3, w2 = _layer(6, jnp.bfloat16)
    got = rx.experts_branch(h, topi, topw, w1, w3, w2)
    want = rx.experts_dense(h, topi, topw, w1, w3, w2)
    assert got.dtype == jnp.bfloat16
    a, b = (np.asarray(x, np.float32) for x in (got, want))
    assert np.abs(a - b).max() < 0.05 * np.abs(b).max()


def test_no_form_holds_an_xla_while_with_absent_picks():
    h, topi, topw, w1, w3, w2 = _layer()
    local = rx.local_expert_ids(topi, 4, 4)
    for form in FORMS:
        jaxpr = jax.make_jaxpr(lambda h: rx.routed_experts(
            form, h, local, topw, w1[4:8], w3[4:8], w2[4:8],
            live=jnp.ones(T, bool), interpret=True))(h)
        assert "while" not in str(jaxpr), form
    # the branch form is four conditionals, one a held expert
    assert str(jaxpr).count("cond[") == 4


# ---- which form a step takes (ISSUE 45: the blast radius) ------------------
# What ``select_moe_impl`` answered at the parent (c4d5fdd) under
# ``auto`` for each token bucket ``benchmark/lattice.buckets`` makes for
# the cells' traffic, and for the fused scan's rows (32 / 64: the
# sequence bucket), called as models/mixtral.py calls it (int8 experts
# stamped for Pallas, rows = tokens x 2) and as models/lfm2_moe.py does
# (bf16 experts, the Pallas backend named, rows = tokens x 4): neither
# passes the router's shape, so neither may ever answer ``branch``.
PARENT_FORMS = {
    "mixtral": {32: "dense", 64: "dense", 128: "dense", 256: "grouped",
                512: "grouped", 1024: "grouped", 2048: "grouped"},
    "lfm2": {64: "dense", 128: "grouped", 256: "grouped", 512: "grouped",
             1024: "grouped", 2048: "grouped"},
}
CELL_TRAFFIC = {"mixtral": "chat-closed-32", "lfm2": "chat-closed-64"}


def _cell_experts(cell):
    """(w1 as ``select_moe_impl`` reads it: shape, dtype and stamp, at
    the cell's published widths; picks a row; the model's keywords)."""
    from vllm_distributed_tpu.ops.quant import QuantizedTensor

    if cell == "mixtral":
        w1 = QuantizedTensor(
            None, None, 8, 0, (8, 4096, 14336), jnp.bfloat16, "pallas")
        return w1, 2, {}
    w1 = jax.ShapeDtypeStruct((32, 2048, 1792), jnp.bfloat16)
    return w1, 4, {"unquantized_backend": "pallas"}


def _lattice_tokens(traffic):
    import json
    import pathlib

    from benchmark import lattice

    path = pathlib.Path(lattice.__file__).parent / "traffic" / (
        traffic + ".json")
    tr = json.loads(path.read_text())
    clients = tr["arrival"]["clients"]
    made = {t for t, _ in lattice.buckets(clients, tr["warmup"]["sweep"])}
    return sorted(made | {clients})  # and the scan's rows


@pytest.mark.parametrize("cell,tokens", [
    (cell, t) for cell, table in PARENT_FORMS.items() for t in table])
def test_the_other_cells_steps_take_the_form_they_took(cell, tokens):
    w1, top_k, kw = _cell_experts(cell)
    got = rx.select_moe_impl("auto", tokens * top_k, w1, on_mesh=False, **kw)
    assert got == PARENT_FORMS[cell][tokens]


@pytest.mark.parametrize("cell", sorted(PARENT_FORMS))
def test_the_table_holds_every_bucket_the_cells_lattice_makes(cell):
    assert _lattice_tokens(CELL_TRAFFIC[cell]) == sorted(PARENT_FORMS[cell])


def _rule_at_the_parent(requested, rows, quantized, grouped_ok):
    """``select_moe_impl`` at c4d5fdd, past its reading of the tensor."""
    if requested == "auto":
        if grouped_ok and rows > rx.GROUPED_MIN_ROWS:
            return "grouped"
        return "dense" if (quantized or rows <= 256) else "ragged"
    if requested == "ragged" and grouped_ok:
        return "grouped"
    return requested


@pytest.mark.parametrize("on_mesh", [False, True])
@pytest.mark.parametrize("cell,backend", [
    ("mixtral", None), ("lfm2", "pallas"), ("lfm2", None)])
def test_without_the_routers_shape_the_choice_is_the_parents(
        cell, backend, on_mesh):
    w1, _, _ = _cell_experts(cell)
    quantized = cell == "mixtral"
    grouped_ok = (quantized or backend == "pallas") and not on_mesh
    for requested in ("auto", "dense", "ragged", "grouped"):
        for rows in (1, 16, 64, 255, 256, 257, 512, 4096, 16384):
            got = rx.select_moe_impl(
                requested, rows, w1, on_mesh, unquantized_backend=backend)
            assert got == _rule_at_the_parent(
                requested, rows, quantized, grouped_ok), (requested, rows)


def test_branch_is_for_few_rows_of_a_wide_router_off_a_mesh():
    """16 held of a 256-wide router, 8 picks a row: 64 rows are
    expected to leave 16 x (31/32)^64 = 2.1 experts unreached (branch),
    128 rows 0.27 (dense as before); never over quantized experts or on
    a mesh, and by name it is the dense einsum there."""
    w1 = jax.ShapeDtypeStruct((16, 7168, 2048), jnp.bfloat16)
    router = dict(top_k=8, router_width=256)

    def choose(tokens, w=w1, on_mesh=False, requested="auto"):
        return rx.select_moe_impl(
            requested, tokens // 2, w, on_mesh,
            unquantized_backend="pallas", tokens=tokens, **router)

    assert [choose(t) for t in (16, 32, 64)] == ["branch"] * 3
    assert [choose(t) for t in (128, 256, 512)] == ["dense"] * 3
    assert [choose(t) for t in (1024, 2048)] == ["grouped"] * 2
    assert 16 * (31 / 32) ** 64 >= rx.BRANCH_MIN_UNREACHED > 16 * (
        31 / 32) ** 128
    assert choose(64, on_mesh=True) == "dense"
    int8, _, _ = _cell_experts("mixtral")
    assert choose(64, w=int8) == "dense"
    assert choose(2048, requested="branch") == "branch"
    assert choose(64, w=int8, requested="branch") == "dense"
    assert choose(64, on_mesh=True, requested="branch") == "dense"
    # a router no wider than the stack with as many rows as the LFM2
    # cell's scan reaches every expert: (28/32)^64 of 32 is 0.006
    lfm2, _, kw = _cell_experts("lfm2")
    assert rx.select_moe_impl(
        "auto", 256, lfm2, False, tokens=64, top_k=4, router_width=32,
        **kw) == "dense"
