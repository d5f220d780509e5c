"""The ragged paged-attention kernel is traced ONCE per step program.

``pl.pallas_call`` traces its kernel's Python body and lowers it to a
kernel module every time it is called, and a decoder calls it once per
layer with identical shapes and parameters.  ``_call`` of
``ops/pallas/paged_attention.py`` therefore sits under an inner
``jax.jit`` keyed on the operand shapes and the kernel's static
parameters: the first layer traces the body, the others share that
trace.  These tests hold the mechanism to what it promises: one body
entry per compiled width whatever the depth, as many kernels in the
program as there are layers, the served tokens of the eager path, and a
cache entry of its own for every geometry and every page block.

Nested under the step's trace the inner jit keeps no executable, so
``_call._cache_size()`` stays 0 there; what counts the traces is the
number of times ``_kernel`` is entered, and that the ``layers`` inner
``jit`` equations of the step's jaxpr hold ONE jaxpr between them.
``_cache_size()`` is read where the kernel is called eagerly.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from examples.lm.model import TransformerLMModel
from unicore_tpu.analysis.trace_audit import _iter_eqns
from unicore_tpu.ops import backend
from unicore_tpu.ops.pallas import paged_attention as pa
from unicore_tpu.serve import Request
from unicore_tpu.serve.engine import ServeEngine

V, F, LAYERS, PAD = 29, 64, 3, 0
CHUNK = 8
POOLS = {"f32": jnp.float32, "bf16": jnp.bfloat16}


@functools.lru_cache(None)
def _lm(heads=4, embed=32):
    model = TransformerLMModel(
        vocab_size=V, padding_idx=PAD, decoder_layers=LAYERS,
        decoder_embed_dim=embed, decoder_ffn_embed_dim=F,
        decoder_attention_heads=heads, max_seq_len=64,
        emb_dropout=0.0, dropout=0.0, attention_dropout=0.0,
        activation_dropout=0.0, rel_pos=False, abs_pos=False, rotary=True,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _engine(model, params, pool="f32", page_size=4, num_pages=24):
    """A toy engine whose weights AND pool are ``pool``'s dtype (the
    engine allocates its pool in the dtype of freshly initialised
    parameters, so a bf16 pool is cast in here)."""
    dtype = POOLS[pool]
    cast = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x.astype(dtype), tree)
    engine = ServeEngine(model, cast(params), num_pages=num_pages,
                         page_size=page_size, max_batch=4,
                         prefill_chunk=CHUNK)
    engine.pages = cast(engine.pages)
    return engine


@pytest.fixture
def kernel_entries(monkeypatch):
    """Every entry of the kernel's Python body, as the keyword arguments
    it was entered with; the trace cache starts empty."""
    entries = []
    inner = pa._kernel

    def counted(*refs, **static):
        entries.append(static)
        return inner(*refs, **static)

    monkeypatch.setattr(pa, "_kernel", counted)
    pa._call.clear_cache()
    yield entries
    pa._call.clear_cache()  # no later test is served the wrapped body


def _kernel_calls(jaxpr):
    """(the ``pallas_call`` equations, the jaxprs of the inner ``jit``
    equations that hold them) anywhere in a traced step."""
    kernels, holders = [], []
    for eqn in _iter_eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            kernels.append(eqn)
        elif (eqn.primitive.name in ("jit", "pjit")
              and eqn.params.get("name") == "_call"):
            holders.append(eqn.params["jaxpr"])
    return kernels, holders


def _prompts():
    trng = np.random.RandomState(11)
    # one prompt longer than a chunk, so both widths serve
    return [trng.randint(1, V, size=(n,)).tolist() for n in (3, 13, 6, 9)]


def _serve(engine):
    reqs = [Request(prompt=p, max_new_tokens=5, seed=i,
                    request_id=f"r{i}")
            for i, p in enumerate(_prompts())]
    return [r.tokens for r in engine.generate(reqs)]


# -- (a) one trace per width, a kernel per layer ---------------------------


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("width", [1, CHUNK])
def test_kernel_body_entered_once_per_width(width, pool, kernel_entries):
    model, params = _lm()
    with backend.kernel_backend("pallas"):
        engine = _engine(model, params, pool)
        assert width in engine.serve_step_widths()
        arts = engine.trace_step_fns(widths=(width,))
        assert len(kernel_entries) == 1, (
            f"{LAYERS} layers entered the kernel body "
            f"{len(kernel_entries)} times")
        kernels, holders = _kernel_calls(arts[f"ragged-w{width}"]["jaxpr"])
        # the audit's walk still reaches a kernel per layer, one jit
        # equation deeper, and the layers hold one trace between them
        assert len(kernels) == LAYERS
        assert len(holders) == LAYERS
        assert len({id(j) for j in holders}) == 1
        # lowering walks the cached jaxpr; it does not call the body
        assert len(kernel_entries) == 1
        # a second engine of the same geometry is served the same trace
        _engine(model, params, pool).trace_step_fns(widths=(width,))
        assert len(kernel_entries) == 1
    assert backend.dispatch_report()["ragged_paged_attention"][
        "b4 w%d h4 d8 page4 %s pp%d slots2" % (
            width, jnp.dtype(POOLS[pool]).name,
            kernel_entries[0]["pages_per_block"])
    ] == "pallas"


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_both_widths_are_two_traces(pool, kernel_entries):
    model, params = _lm()
    with backend.kernel_backend("pallas"):
        engine = _engine(model, params, pool)
        engine.trace_step_fns()
    assert len(engine.serve_step_widths()) == 2
    assert len(kernel_entries) == 2


# -- (b) the served tokens are the eager path's ----------------------------


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_served_tokens_match_reference_backend(pool, kernel_entries):
    model, params = _lm()
    with backend.kernel_backend("reference"):
        want = _serve(_engine(model, params, pool))
    assert kernel_entries == []
    with backend.kernel_backend("pallas"):
        engine = _engine(model, params, pool)
        got = _serve(engine)
    assert got == want
    assert all(len(t) == 5 for t in got)
    # both widths served, each off one trace
    assert engine.stats["prefills"] and engine.stats["decode_steps"]
    assert len(kernel_entries) == 2


# -- (c) another geometry is another entry ---------------------------------


@pytest.mark.parametrize("other", ["page_size", "heads"])
def test_engines_of_other_geometry_get_their_own_trace(other,
                                                       kernel_entries):
    """Two engines in one process that differ in ``page_size`` or in
    the head count trace a kernel each, and each serves the tokens of
    its own eager path: neither is handed the other's trace."""
    a = dict(heads=4, page_size=4)
    b = dict(a, page_size=8) if other == "page_size" else dict(a, heads=2)
    def serve(geo, kernels):
        model, params = _lm(heads=geo["heads"])
        with backend.kernel_backend(kernels):
            return _serve(_engine(model, params,
                                  page_size=geo["page_size"]))

    # back to the first geometry at the end: its entry is still there
    got = [serve(geo, "pallas") for geo in (a, b, a)]
    want = [serve(geo, "reference") for geo in (a, b)]
    assert got == want + want[:1]
    # two widths x two geometries; the third engine traced nothing
    assert len(kernel_entries) == 4
    seen = {(e["page_size"], e["heads"], e["head_dim"])
            for e in kernel_entries}
    assert seen == {(g["page_size"], g["heads"], 32 // g["heads"])
                    for g in (a, b)}


# -- (d) the shape rule's page block reaches the kernel --------------------


def test_picked_pages_per_block_reaches_the_kernel(kernel_entries):
    model, params = _lm()
    with backend.kernel_backend("pallas"):
        engine = _engine(model, params)
        picked = pa.pick_pages_per_block(
            engine.table_width, engine.page_size, 8, num_heads=4,
            itemsize=4)
        assert picked != 1
        engine.trace_step_fns(widths=(1,))
    assert [e["pages_per_block"] for e in kernel_entries] == [picked]


def test_eager_calls_cache_one_entry_per_page_block(rng, kernel_entries):
    """Called eagerly (the kernel's own tests, a scratch sweep)
    the inner jit keeps its executables, so ``_cache_size()`` counts
    them: one per ``pages_per_block``, none for a repeated call."""
    bsz, pages, ps, heads, d = 2, 4, 4, 4, 8
    slots = (bsz * pages + 1) * ps
    k = jnp.asarray(rng.randn(slots, heads * d), jnp.float32)
    v = jnp.asarray(rng.randn(slots, heads * d), jnp.float32)
    table = jnp.arange(1, bsz * pages + 1, dtype=jnp.int32).reshape(
        bsz, pages)
    lengths = jnp.asarray([13, 6], jnp.int32)
    q = jnp.asarray(rng.randn(bsz, 1, heads, d), jnp.float32)
    outs = [
        pa.ragged_decode_attention(q, k, v, table, lengths, page_size=ps,
                                   scale=d ** -0.5, pages_per_block=pp)
        for pp in (1, 2, 4, 2)
    ]
    assert pa._call._cache_size() == 3
    assert [e["pages_per_block"] for e in kernel_entries] == [1, 2, 4]
    for out in outs[1:]:
        np.testing.assert_allclose(np.asarray(out), np.asarray(outs[0]),
                                   atol=2e-5, rtol=2e-5)
