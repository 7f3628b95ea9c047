"""The routed layer every model shares (models/routed_experts.py) when
a pick may belong to no expert held here (ISSUE 43): indices of E or
above have no column in the dense form's combine matrix, join no group
in the sorted forms and add nothing, in all three forms against the
dense oracle over the whole router; with every pick held the forms are
what they were."""

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
    """The three forms over one held stack (the grouped kernel
    interpreted)."""
    return {
        "dense": rx.experts_dense(h, local, topw, w1, w3, w2),
        "ragged": rx.experts_ragged(h, local, topw, w1, w3, w2, live),
        "grouped": rx.experts_grouped(
            h, local, topw, w1, w3, w2, live, interpret=True),
    }


def test_local_ids_send_every_absent_pick_past_the_stack():
    topi = jnp.asarray([[0, 3, 4], [7, 8, 11], [5, 6, 2]], jnp.int32)
    got = np.asarray(rx.local_expert_ids(topi, 4, 4))
    assert got.tolist() == [[4, 4, 0], [3, 4, 4], [1, 2, 4]]
    assert got.dtype == np.int32
    # rank 0 keeps its ids; nothing goes negative (an index of -1 would
    # wrap to the last held expert in a scatter)
    assert np.asarray(rx.local_expert_ids(topi, 0, 4)).min() == 0


@pytest.mark.parametrize("form", ["dense", "ragged", "grouped"])
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


@pytest.mark.parametrize("form", ["dense", "ragged", "grouped"])
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


@pytest.mark.parametrize("form", ["ragged", "grouped"])
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


@pytest.mark.parametrize("form", ["ragged", "grouped"])
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
    with jax.default_matmul_precision("highest"):
        is_held = (topi >= lo) & (topi < lo + held) & live[:, None]
        want = np.asarray(rx.experts_dense(
            h, topi, jnp.where(is_held, topw, 0.0), w1, w3, w2))
        stack = (w[lo:lo + held] for w in (w1, w3, w2))
        got = np.asarray(_forms(h, local, topw, *stack, live)[form])
    assert not got[17:].any()
    assert np.abs(got - want).max() < 1e-5


@pytest.mark.parametrize("form", ["dense", "ragged", "grouped"])
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


def test_bfloat16_rows_through_the_grouped_kernel_with_absent_picks():
    """The cell's dtype: bf16 rows and experts through the interpreted
    ``vdt_grouped_matmul``, absent picks among them, against the dense
    form in bf16 (both round each product; they differ by rounding)."""
    h, topi, topw, w1, w3, w2 = _layer(5, jnp.bfloat16)
    local = rx.local_expert_ids(topi, 4, 4)
    live = jnp.ones(T, bool)
    stack = [w[4:8] for w in (w1, w3, w2)]
    got = _forms(h, local, topw, *stack, live)
    assert got["grouped"].dtype == jnp.bfloat16
    a, b = (np.asarray(got[f], np.float32) for f in ("grouped", "dense"))
    assert np.abs(a - b).max() < 0.05 * np.abs(b).max()


def test_no_form_holds_an_xla_while_with_absent_picks():
    h, topi, topw, w1, w3, w2 = _layer()
    local = rx.local_expert_ids(topi, 4, 4)
    for form in ("dense", "ragged", "grouped"):
        jaxpr = jax.make_jaxpr(lambda h: rx.routed_experts(
            form, h, local, topw, w1[4:8], w3[4:8], w2[4:8],
            live=jnp.ones(T, bool), interpret=True))(h)
        assert "while" not in str(jaxpr), form
