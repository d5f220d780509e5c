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


# -- a share of the experts beside a shared expert (PR 35) ------------------


@pytest.mark.parametrize("eps", [1e-6, 1e-20])
def test_route_divides_with_the_models_epsilon(eps):
    """Scores small enough for the epsilon to show: 1e-6 under the sum
    pulls the weights off 1, 1e-20 does not."""
    scores = jnp.asarray([[3e-6, 1e-6, 5e-7, 0.0]], jnp.float32)
    _, w = moe.route(scores, None, 2, eps=eps)
    np.testing.assert_allclose(
        w, np.asarray([[3e-6, 1e-6]]) / (4e-6 + eps), rtol=1e-5)
    assert (abs(float(w.sum()) - 1.0) < 1e-5) == (eps == 1e-20)


def test_the_scores_alone_choose_without_a_bias():
    """``use_bias=False``: the layer has no ``expert_bias`` parameter and
    the selection is the top of the scores themselves."""
    rng = np.random.default_rng(9)
    x = rng.normal(size=(1, N, D)).astype(np.float32)
    module = ExpertFFN(D, ExpertSpec(E, K, F, use_bias=False, scale=2.5,
                                     eps=1e-20))
    params = module.init(jax.random.PRNGKey(0), x)["params"]
    assert sorted(params) == ["router", "w1", "w2", "w3"]
    params = {k: jnp.asarray(0.3 * rng.normal(size=v.shape), jnp.float32)
              for k, v in params.items()}
    scores = jax.nn.sigmoid(jnp.asarray(x[0] @ np.asarray(params["router"])))
    sel, w = moe.route(scores, None, K, 2.5, 1e-20)
    want = dense_oracle(x[0], np.ones(N, bool), np.asarray(params["w1"]),
                        np.asarray(params["w3"]), np.asarray(params["w2"]),
                        sel, w)
    got = module.apply({"params": params}, x)[0]
    assert np.abs(got - want).max() < TOL


def _pangu_layer(rng, experts, first=0, held=0):
    spec = ExpertSpec(experts, 2, F, use_bias=False, scale=2.5,
                      first_expert=first, experts_held=held, eps=1e-20,
                      shared_experts=1)
    return ExpertFFN(D, spec)


def test_four_shares_and_the_shared_expert_counted_once_are_the_layer():
    """4 shares of 2 of 8 experts, each beside the whole shared expert:
    the routed parts add up to the routed sum of the uncut layer, and with
    the shared expert counted ONCE that is the uncut reference's layer
    (``benchmarks/reference/pangu_moe_lm.py`` given all 8 experts)."""
    from benchmarks.reference import pangu_moe_lm

    experts, held = 8, 2
    rng = np.random.default_rng(10)
    f = lambda *s: jnp.asarray(0.3 * rng.normal(size=s), jnp.float32)
    x = f(1, N, D)
    tree = {"router": f(D, experts), "w1": f(experts, D, F),
            "w3": f(experts, D, F), "w2": f(experts, F, D),
            "shared_experts": {"gate_proj": {"kernel": f(D, F)},
                               "up_proj": {"kernel": f(D, F)},
                               "down_proj": {"kernel": f(F, D)}}}
    whole = pangu_moe_lm.expert_ffn(x[0], tree, top_k=2, scale=2.5,
                                    first_expert=0, precision="fp32")
    shared = pangu_moe_lm.swiglu_by_rows(x[0], tree["shared_experts"], "fp32")
    routed, ref_routed = [], []
    for first in range(0, experts, held):
        cut = {**tree, **{k: tree[k][first:first + held]
                          for k in ("w1", "w3", "w2")}}
        got = _pangu_layer(rng, experts, first, held).apply(
            {"params": cut}, x)[0]
        routed.append(got - shared)
        ref_routed.append(pangu_moe_lm.expert_ffn(
            x[0], cut, top_k=2, scale=2.5, first_expert=first,
            precision="fp32") - shared)
    for parts in (routed, ref_routed):
        assert np.abs(shared + sum(parts) - whole).max() < TOL
    uncut = _pangu_layer(rng, experts).apply({"params": tree}, x)[0]
    assert np.abs(uncut - whole).max() < TOL
    # one share alone is not the layer, and the shared expert is in it
    assert np.abs(shared + routed[0] - whole).max() > 100 * TOL
    assert np.abs(np.asarray(shared)).max() > 100 * TOL


def test_a_step_in_which_no_held_expert_got_a_token_runs_no_block(
        monkeypatch):
    """Every token chooses experts 0 and 1; the share holds 6 and 7: the
    loop over blocks has nothing to run, the part is zero, the load is
    zero, and the lowered loop's trip count is the blocks USED (a traced
    value), not the blocks the buffers could hold."""
    rng = np.random.default_rng(11)
    x = rng.normal(size=(N, D)).astype(np.float32)
    w1, w3, w2 = _weights(rng, 2)
    scores = jnp.asarray(np.tile([0.9, 0.8] + [0.1] * 6, (N, 1)), jnp.float32)
    sel, w = moe.route(scores, None, 2)
    y, load = moe.expert_ffn(x, None, w1, w3, w2, sel, w, first_expert=6,
                             num_experts=8)
    assert not np.asarray(y).any() and load.tolist() == [0, 0]
    blocks = []
    real = jax.lax.fori_loop

    def counting(lo, hi, body, init):
        blocks.append(hi)
        return real(lo, hi, body, init)

    monkeypatch.setattr(jax.lax, "fori_loop", counting)
    for first in (6, 0):
        moe.expert_ffn(x, None, w1, w3, w2, sel, w, first_expert=first,
                       num_experts=8)
    # 37 tokens on each of experts 0 and 1, blocks of 24 rows: 2 + 2
    assert [int(b) for b in blocks] == [0, 4]


@pytest.mark.parametrize("tokens,k,held,experts,rows", [
    (512, 8, 8, 256, 32),      # the cell's mixed list: 128 of 4,096 land here
    (16, 8, 8, 256, 8),        # its decode step: 4 of 128
    (512, 4, 64, 64, 64),      # every expert held: as before
    (37, 2, 2, 8, 24),
])
def test_a_share_sizes_its_blocks_from_the_choices_it_can_expect(
        tokens, k, held, experts, rows):
    rng = np.random.default_rng(12)
    x = rng.normal(size=(tokens, 8)).astype(np.float32)
    w = lambda *s: np.zeros(s, np.float32)
    sel, wt = moe.route(jnp.full((tokens, experts), 0.5), None, k)
    jax.eval_shape(lambda: moe.expert_ffn(
        x, None, w(held, 8, 16), w(held, 8, 16), w(held, 16, 8), sel, wt,
        num_experts=experts))
    seen = backend.dispatch_report()["moe_experts"]
    assert f"n{tokens} k{k} e{held} d8 f16 blk{rows} float32" in seen


def test_a_share_still_serves_every_choice_that_lands_on_it():
    """Blocks are sized for an eighth of the choices, the buffers for all
    of them: when EVERY token chooses the two held experts nothing is
    dropped."""
    rng = np.random.default_rng(13)
    x = rng.normal(size=(N, D)).astype(np.float32)
    w1, w3, w2 = _weights(rng, 2)
    scores = jnp.asarray(np.tile([0.1] * 4 + [0.9, 0.8] + [0.1] * 10,
                                 (N, 1)), jnp.float32)
    sel, w = moe.route(scores, None, 2)
    y, load = moe.expert_ffn(x, None, w1, w3, w2, sel, w, first_expert=4,
                             num_experts=16)
    assert load.tolist() == [N, N]
    want = dense_oracle(x, np.ones(N, bool), w1, w3, w2, sel, w, first=4)
    assert np.abs(y - want).max() < TOL
