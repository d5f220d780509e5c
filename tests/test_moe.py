"""The sparse-expert ops (``unicore_tpu/ops/moe.py``) against NumPy
oracles that share nothing with them: a dense ``[tokens, experts]``
weight matrix, every expert applied to every token.

Tolerance: both sides are float32 on the CPU and differ in the ORDER of
the sums (the op multiplies a token's row inside its expert's block and
sums its ``top_k`` parts; the oracle sums over all experts with zeros),
so a few ulps of values of order 1: ``TOL = 2e-5``.  A token that reached
a wrong expert, or none, moves its output by order 1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_moe_lm as reference
from unicore_tpu.modules import ExpertFFN, ExpertSpec
from unicore_tpu.ops import backend, moe

TOL = 2e-5
N, D, F, E, K = 37, 16, 24, 8, 3


def _weights(rng, experts=E):
    f = lambda *s: (0.3 * rng.normal(size=s)).astype(np.float32)
    return f(experts, D, F), f(experts, D, F), f(experts, F, D)


def _silu(a):
    return a / (1.0 + np.exp(-a))


def dense_oracle(x, valid, w1, w3, w2, sel, w, first=0):
    """Every held expert on every token, weighted by a dense matrix that
    is 0 where the token did not choose it (or is not valid)."""
    dense = np.zeros((x.shape[0], first + w1.shape[0] + 64), np.float32)
    np.put_along_axis(dense, np.asarray(sel), np.asarray(w), axis=1)
    dense *= np.asarray(valid, np.float32)[:, None]
    return sum(dense[:, first + e:first + e + 1]
               * ((_silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
               for e in range(w1.shape[0]))


def route_oracle(scores, bias, k):
    chosen_by = scores + (0 if bias is None else bias)
    # a stable sort on the negated key: ties go to the lower index
    sel = np.argsort(-chosen_by, axis=1, kind="stable")[:, :k]
    w = np.take_along_axis(scores, sel, axis=1)
    return sel, w / (w.sum(axis=1, keepdims=True) + 1e-6)


@pytest.mark.parametrize("with_bias", [True, False])
@pytest.mark.parametrize("seed", [0, 1])
def test_route_against_the_oracle(seed, with_bias):
    rng = np.random.default_rng(seed)
    scores = 1 / (1 + np.exp(-rng.normal(size=(N, E)))).astype(np.float32)
    bias = rng.normal(size=E).astype(np.float32) if with_bias else None
    sel, w = moe.route(jnp.asarray(scores),
                       None if bias is None else jnp.asarray(bias), K)
    want_sel, want_w = route_oracle(scores, bias, K)
    assert np.array_equal(sel, want_sel) and sel.dtype == jnp.int32
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(1), 1.0, atol=1e-5)


def test_the_bias_moves_the_selection_and_not_the_weights():
    scores = jnp.asarray([[0.9, 0.8, 0.3, 0.2]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0], jnp.float32)
    sel, w = moe.route(scores, bias, 2)
    assert sel.tolist() == [[3, 0]]        # 0.2 + 1 leads, then 0.9
    # the weights are the SCORES of the chosen, renormalised: 0.2 and 0.9
    np.testing.assert_allclose(w, [[0.2 / 1.1, 0.9 / 1.1]], rtol=1e-5)
    plain, _ = moe.route(scores, None, 2)
    assert plain.tolist() == [[0, 1]]
    scaled = moe.route(scores, bias, 2, scale=2.5)[1]
    np.testing.assert_allclose(scaled, 2.5 * np.asarray(w), rtol=1e-6)


def test_ties_are_broken_as_top_k_breaks_them():
    scores = jnp.full((3, 6), 0.5, jnp.float32)
    sel, w = moe.route(scores, None, 4)
    assert sel.tolist() == [[0, 1, 2, 3]] * 3      # the lower index first
    np.testing.assert_allclose(w, 0.25, atol=1e-6)


LOADS = {
    # name: selection scores' shape over the experts
    "even": lambda rng: rng.normal(size=(N, E)),
    "skewed": lambda rng: rng.normal(size=(N, E)) + 2.0 * (np.arange(E) < 2),
    "one_hot": lambda rng: 5.0 * (np.arange(E) < K) + 0.01 * rng.normal(
        size=(N, E)),
}


@pytest.mark.parametrize("block_rows", [None, 8, 16])
@pytest.mark.parametrize("load", sorted(LOADS))
def test_expert_ffn_against_the_dense_weight_oracle(load, block_rows):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(N, D)).astype(np.float32)
    w1, w3, w2 = _weights(rng)
    scores = jax.nn.sigmoid(jnp.asarray(LOADS[load](rng), jnp.float32))
    sel, w = moe.route(scores, None, K)
    valid = np.ones(N, bool)
    y, counted = jax.jit(
        lambda *a: moe.expert_ffn(*a, block_rows=block_rows))(
        x, jnp.asarray(valid), w1, w3, w2, sel, w)
    assert np.abs(y - dense_oracle(x, valid, w1, w3, w2, sel, w)).max() < TOL
    assert counted.tolist() == np.bincount(
        np.asarray(sel).ravel(), minlength=E).tolist()
    if load == "one_hot":
        # every token on the same K experts: no capacity, nobody dropped,
        # and the other experts got nothing
        assert counted.tolist() == [N] * K + [0] * (E - K)


def test_cells_nobody_carries_reach_no_expert_and_no_counter():
    """The serve step's list is mostly empty cells: they hold whatever
    was there (here NaN), are routed nowhere, count nowhere and come back
    zero, and the cells that are carried are what they would be alone."""
    rng = np.random.default_rng(4)
    x = rng.normal(size=(N, D)).astype(np.float32)
    w1, w3, w2 = _weights(rng)
    valid = rng.random(N) > 0.6
    x_dirty = np.where(valid[:, None], x, np.nan).astype(np.float32)
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(N, E)), jnp.float32))
    sel, w = moe.route(scores, None, K)
    y, counted = moe.expert_ffn(x_dirty, jnp.asarray(valid), w1, w3, w2,
                                sel, w)
    assert np.isfinite(np.asarray(y)).all()
    assert not np.asarray(y)[~valid].any()
    assert np.abs(y - dense_oracle(x, valid, w1, w3, w2, sel, w)).max() < TOL
    assert counted.tolist() == np.bincount(
        np.asarray(sel)[valid].ravel(), minlength=E).tolist()
    nobody = moe.expert_ffn(x_dirty, jnp.zeros(N, bool), w1, w3, w2, sel, w)
    assert not np.asarray(nobody[0]).any() and not np.asarray(nobody[1]).any()


@pytest.mark.parametrize("shares", [8, 2, 1])
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(shares):
    """``shares`` chips each hold ``64 / shares`` experts of one layer:
    every share routes over all 64 and computes its own experts' part;
    the parts add up to the whole layer, in the op, in the module and in
    the plain reference given the same share."""
    experts, k = 64, 4
    rng = np.random.default_rng(5)
    x = rng.normal(size=(N, D)).astype(np.float32)
    w1, w3, w2 = _weights(rng, experts)
    router = rng.normal(size=(D, experts)).astype(np.float32)
    bias = (0.3 * rng.normal(size=experts)).astype(np.float32)
    scores = jax.nn.sigmoid(jnp.asarray(x @ router))
    sel, w = moe.route(scores, jnp.asarray(bias), k)
    whole = dense_oracle(x, np.ones(N, bool), w1, w3, w2, sel, w)
    held = experts // shares
    parts, loads, ref_parts, module_parts = [], [], [], []
    for first in range(0, experts, held):
        cut = slice(first, first + held)
        y, load = moe.expert_ffn(x, None, w1[cut], w3[cut], w2[cut], sel, w,
                                 first_expert=first)
        parts.append(y)
        loads += load.tolist()
        tree = {"router": router, "expert_bias": bias, "w1": w1[cut],
                "w3": w3[cut], "w2": w2[cut]}
        ref_parts.append(reference.expert_ffn(
            jnp.asarray(x), tree, top_k=k, scale=1.0, first_expert=first,
            precision="fp32"))
        module = ExpertFFN(D, ExpertSpec(experts, k, F, first_expert=first,
                                         experts_held=held))
        module_parts.append(module.apply({"params": tree}, x[None])[0])
    assert loads == np.bincount(np.asarray(sel).ravel(),
                                minlength=experts).tolist()
    for summed in (sum(parts), sum(ref_parts), sum(module_parts)):
        assert np.abs(summed - whole).max() < TOL
    if shares > 1:  # one share alone is NOT the layer
        assert np.abs(parts[0] - whole).max() > 100 * TOL


@pytest.mark.parametrize("assignments,held,rows", [
    (32 * 4, 64, 8),        # the benchmark cell's decode step
    (512 * 4, 64, 64),      # its mixed step
    (2, 64, 8), (4096 * 8, 8, 128), (100, 8, 32),
])
def test_block_rows_from_shape(assignments, held, rows):
    assert moe.pick_block_rows(assignments, held) == rows
    assert rows % moe.SUBLANES == 0


def test_the_op_is_in_the_dispatch_report_and_the_routing_record():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(5, D)).astype(np.float32)
    sel, w = moe.route(jnp.full((5, E), 0.5), None, K)
    moe.expert_ffn(x, None, *_weights(rng), sel, w)
    seen = backend.dispatch_report()["moe_experts"]
    assert seen[f"n5 k{K} e{E} d{D} f{F} blk8 float32"] == "reference"
    before = moe.routing_report()
    moe.note_routing(12, 7)
    after = moe.routing_report()
    assert after[-1] == (12, 7) and after[:-1] == before[-len(after) + 1:]
    assert after is not moe.routing_report()      # a copy
