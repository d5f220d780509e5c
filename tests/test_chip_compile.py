"""What the chip's compiler accepts: the main path's Pallas kernels,
compiled at their production shapes for a v5e that is DESCRIBED, not
attached (``on-chip-measurement`` guide, section 2, rehearsal 3).

Interpret-mode tests cannot see a Mosaic refusal — a block spec the TPU
lowering rejects, a DMA slice that is not tile-aligned, more scoped VMEM
than a kernel may use — and the dispatch has no fallback behind it, so a
refusal here is a run that dies on the chip.  A compile that passes is
not a chip run: ``chip_smoke.py`` is.

The topology is described inside a module-scoped fixture (one process
holds libtpu; nothing may touch it at import or collection time), the
kernels are steered to their compiled (not interpreted) form by
monkeypatching ``backend._on_tpu`` here in the test, and the persistent
compilation cache is off around the whole file (an executable compiled
for a described chip cannot be read back without one).
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels lower for the chip (``interpret=False``) although the
    process's default backend is the CPU."""
    from unicore_tpu.ops import backend

    on_tpu = functools.lru_cache(None)(lambda: True)
    monkeypatch.setattr(backend, "_on_tpu", on_tpu)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


BF16, F32, I32, U32 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.uint32

# name -> (batch, seq, heads, head_dim, dtype, bias, pad, causal,
#          dropout, backward)
FLASH_CASES = {
    # the BERT-base train step: trainable [1, H, T, T] bias + padding
    # mask + dropout, head-batched single-block kernels, at batch 64
    # (the scoped-VMEM watch point) and at chip_smoke's batch 32
    "bert_b64_bias_pad_dropout": (64, 512, 12, 64, BF16, True, True,
                                  False, True, True),
    "bert_b32_bias_pad_dropout": (32, 512, 12, 64, BF16, True, True,
                                  False, True, True),
    "bert_b64_pad_dropout": (64, 512, 12, 64, BF16, False, True, False,
                             True, True),
    # the fp32 forward the trainer traces at parameter init
    "bert_init_f32_forward": (32, 512, 12, 64, F32, True, True, False,
                              False, False),
    # transformer_lm_base training, and the full forward the serve
    # check compares with
    "lm_b8_causal_pad_dropout": (8, 512, 12, 64, BF16, False, True, True,
                                 True, True),
    "lm_f32_causal_forward": (1, 256, 12, 64, F32, False, True, True,
                              False, False),
    # long context: multi-block causal
    "causal_t8192": (1, 8192, 12, 64, BF16, False, False, True, False,
                     True),
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_attention_compiles(case, one_chip, compiled_kernels):
    from unicore_tpu.ops.pallas.flash_attention import flash_attention

    b, t, h, d, dt, has_bias, has_pad, causal, dropout, bwd = (
        FLASH_CASES[case])

    def f(q, k, v, bias, pad, key):
        out = flash_attention(
            q, k, v, bias=bias if has_bias else None,
            key_padding_mask=pad if has_pad else None, causal=causal,
            dropout_prob=0.1 if dropout else 0.0,
            rng=key if dropout else None, is_training=dropout,
        )
        return jnp.sum(out.astype(jnp.float32))

    fn = jax.grad(f, argnums=(0, 1, 2, 3)) if bwd else f
    qkv = ((b, t, h, d), dt)
    _compile(fn, one_chip, qkv, qkv, qkv, ((1, h, t, t), dt),
             ((b, t), I32), ((2,), U32))


def test_softmax_dropout_compiles(one_chip, compiled_kernels):
    from unicore_tpu.ops.pallas.softmax_dropout import softmax_dropout

    def f(x, bias, key):
        return jnp.sum(softmax_dropout(
            x, 0.1, rng=key, is_training=True, bias=bias,
        ).astype(jnp.float32))

    _compile(jax.grad(f, argnums=(0, 1)), one_chip,
             ((64, 12, 512, 512), BF16), ((1, 12, 512, 512), BF16),
             ((2,), U32))


def test_sr_rounding_compiles(one_chip, compiled_kernels):
    from unicore_tpu.ops.pallas.rounding import fp32_to_bf16_sr

    _compile(fp32_to_bf16_sr, one_chip, ((768 * 768,), F32), ((2,), U32))


# the serve engine's two compiled widths (decode, prefill chunk) at
# max_batch 8, for the serve CLI's default page size and chip_smoke's,
# the head counts of transformer_lm (8) and transformer_lm_base (12),
# and both pool dtypes (a checkpoint serves its fp32 master params)
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("heads", [12, 8])
@pytest.mark.parametrize("page_size", [16, 64])
@pytest.mark.parametrize("width", [1, 32, 64])
def test_ragged_paged_attention_compiles(width, page_size, heads, dtype,
                                         one_chip, compiled_kernels):
    from unicore_tpu.ops.pallas import paged_attention as pa

    bsz, d, context, num_pages = 8, 64, 512, 64
    assert pa.supported(heads, d, page_size, jnp.dtype(dtype).itemsize)
    fn = functools.partial(
        pa.ragged_paged_attention, page_size=page_size, scale=d ** -0.5)
    pool = ((num_pages * page_size, heads * d), dtype)
    _compile(fn, one_chip, ((bsz, width, heads, d), dtype), pool, pool,
             ((bsz, context // page_size), I32), ((bsz, width), I32),
             ((bsz,), I32))


# the kernel's shapes in the serve configurations, with what the shape
# rules give each: (rows, query cells, K/V heads, head dim, pool pages,
# table pages, three passes) -> (pages a block, scoped VMEM in MB).  The
# second K/V slot is paid by ``vmem_limit_bytes``, not by smaller blocks:
# every shape keeps the 4 pages of 64 it walked with one slot, and the
# programs whose buffers pass the chip's default of 16 MB (the mixed ones
# of ``opt_1.3b`` and both of the hybrid's 3,840 lanes) ask for more
SERVE_KERNEL_SHAPES = {
    "opt_1.3b-w1": ((32, 1, 32, 64, 288, 32, False), (4, 16.0)),
    "opt_1.3b-w128": ((32, 128, 32, 64, 288, 32, False), (4, 23.0)),
    "olmo_hybrid_7b-w1": ((12, 1, 30, 128, 800, 128, False), (4, 21.31)),
    "olmo_hybrid_7b-w64": ((12, 64, 30, 128, 800, 128, False), (4, 27.56)),
    "lfm2_24b_a2b-w1": ((32, 4, 8, 64, 800, 32, False), (4, 16.0)),
    "lfm2_24b_a2b-w128": ((32, 512, 8, 64, 800, 32, False), (4, 17.0)),
    "openpangu-w1": ((16, 128, 1, 640, 1152, 132, True), (4, 16.0)),
    "openpangu-w64": ((256, 512, 1, 640, 1152, 132, True), (4, 16.0)),
}


@pytest.mark.parametrize("shape", sorted(SERVE_KERNEL_SHAPES))
def test_serve_kernel_pages_per_block_and_vmem_limit(shape):
    from unicore_tpu.ops.pallas import paged_attention as pa

    (_, cells, heads, d, _, table, _), (pages, limit_mb) = (
        SERVE_KERNEL_SHAPES[shape])
    pp = pa.pick_pages_per_block(table, 64, d, num_heads=heads, itemsize=4)
    assert pp == pages
    limit = pa.vmem_limit_bytes(cells, heads * d, heads, pp * 64, 4, 4)
    assert round(limit / 2 ** 20, 2) == limit_mb
    # both slots' blocks are inside it, and it is a fraction of a v5e's
    # 128 MB of VMEM
    assert pa.SLOTS * 2 * pp * 64 * heads * d * 4 < limit <= 32 << 20


# ``opt_1.3b``'s two programs (32 heads x 64 in two-head slabs, a float32
# pool of 288 pages, 32 rows, a table of 32 pages), the hybrid's (30 heads
# of 128: a one-head slab, 12 rows, an 8,192-token table, 800 pages) and
# the other configurations' kernels on their own; the step programs of
# ``lfm2_24b_a2b`` and the latent step compile whole further down
@pytest.mark.parametrize("shape", sorted(SERVE_KERNEL_SHAPES))
def test_serve_kernel_shapes_compile_with_two_slots(shape, one_chip,
                                                    compiled_kernels):
    from unicore_tpu.ops.pallas import paged_attention as pa

    rows, cells, heads, d, num_pages, table, three_pass = (
        SERVE_KERNEL_SHAPES[shape][0])
    assert pa.supported(heads, d, 64, 4)
    fn = functools.partial(
        pa.ragged_paged_attention, page_size=64, scale=d ** -0.5,
        three_pass=three_pass)
    pool = ((num_pages * 64, heads * d), F32)
    text = _compile(fn, one_chip, ((rows, cells, heads, d), F32), pool, pool,
                    ((rows, table), I32), ((rows, cells), I32),
                    ((rows,), I32))
    assert text.count("tpu_custom_call") == 1  # still one Mosaic kernel


# the cell `lfm2_moe_longgen` whole: both step programs of the engine at
# the configuration's own shapes (32 rows; lists of 32 and 512 tokens; 64
# experts of 2048 x 1536 in four layers; grouped queries folded into 4 and
# 512 query cells a row of the ragged kernel).  What has to hold on the
# chip: the kernel takes the folded rows, the loop over expert blocks
# compiles, and arguments + temporaries fit the chip's 16 GB beside each
# other (10.8 GB of float32 weights)
@pytest.mark.parametrize("width", [1, 128])
def test_lfm2_moe_step_programs_compile(width, one_chip, compiled_kernels):
    import json
    import os

    from benchmarks.lib import serve_cell, spec
    from unicore_tpu.serve import ServeEngine

    cell = spec.load_cell("lfm2_moe_longgen")
    cfg = cell["config"]
    model = cell["family"].build_model(cfg)
    abstract = serve_cell.abstract_params(model)
    eng = ServeEngine(model, abstract, **cfg["engine"])
    assert eng.serve_step_widths() == (1, 128) and eng.mixed_tokens == 512
    placed = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    packed = jax.ShapeDtypeStruct(
        (eng._packed_size(eng._step_operands(width)),), I32,
        sharding=one_chip)
    prev = jax.ShapeDtypeStruct((eng._out_size(),), I32, sharding=one_chip)
    compiled = eng._ragged_step_fn(width, "greedy").lower(
        placed(abstract), placed(eng.pages), packed, prev).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 1      # the one attention layer
    assert len([l for l in text.splitlines() if " while(" in l]) == 4
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 10.8e9 < mem.argument_size_in_bytes < 11.3e9, mem
    assert held < 14e9, mem


# the cell `pangu_mla_shareddocs` whole: both step programs of the engine
# at the configuration's own shapes (16 rows x 64; lists of 16 and 512 tokens;
# five latent-attention layers whose heads ride as 128 and 512 query cells
# a row of the ragged kernel over one K/V head of 640 lanes, the page table
# repeated once a tile; a shared expert beside 8 of 256 routed experts in
# four layers).  What has to hold on the chip: the kernel takes the cells
# and the repeated table (VMEM, SMEM), the loop over expert blocks
# compiles, and arguments + temporaries fit the chip's 16 GB beside each
# other (13.64 GB of float32 weights, 0.94 GB of latent pages)
@pytest.mark.parametrize("width", [1, 64])
def test_pangu_mla_step_programs_compile(width, one_chip, compiled_kernels):
    from benchmarks.lib import serve_cell, spec
    from unicore_tpu.serve import ServeEngine

    cell = spec.load_cell("pangu_mla_shareddocs")
    cfg = cell["config"]
    model = cell["family"].build_model(cfg)
    abstract = serve_cell.abstract_params(model)
    eng = ServeEngine(model, abstract, **cfg["engine"])
    assert eng.serve_step_widths() == (1, 64) and eng.mixed_tokens == 512
    assert eng.stats["cache_bytes_per_token"] == 5 * 640 * 4
    placed = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    packed = jax.ShapeDtypeStruct(
        (eng._packed_size(eng._step_operands(width)),), I32,
        sharding=one_chip)
    prev = jax.ShapeDtypeStruct((eng._out_size(),), I32, sharding=one_chip)
    compiled = eng._ragged_step_fn(width, "greedy").lower(
        placed(abstract), placed(eng.pages), packed, prev).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5       # a kernel a layer
    assert len([l for l in text.splitlines() if " while(" in l]) == 4
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 14.5e9 < mem.argument_size_in_bytes < 14.7e9, mem
    assert held < 16e9, mem


# the cell `laguna_swa_mixedlen`: the ragged kernel at the shapes the two
# kinds of layer hand it (8 K/V heads of 128; 6 or 8 query cells a
# position, so 768 / 1,024 cells a row of a mixed step; a global layer
# over a table of 264 pages, a sliding one with its window over a row's
# 11), in THREE passes (this model's step's rule), with what the shape
# rules give each: 4 pages a block, and a scoped VMEM of up to 52 MB of the
# chip's 128 (queries and outputs of 1,024 cells x 1,024 lanes are 4 MB a
# buffer, the running max and sum of 8 heads 4 MB each, and 14 MB for the
# three-pass temporaries of the unrolled slabs).  The whole step programs were compiled for the
# described chip by hand (PERF.md section 6, PR 43: arguments 13.24 GB,
# temporaries 0.42 GB); here the kernels alone, two seconds each
WINDOW_KERNEL_SHAPES = {
    "global-w1": ((32, 6, 3000, 264, 0), 16.0),
    "global-w128": ((32, 768, 3000, 264, 0), 41.5),
    "sliding-w1": ((32, 8, 329, 11, 512), 16.0),
    "sliding-w128": ((32, 1024, 329, 11, 512), 52.0),
}


@pytest.mark.parametrize("shape", sorted(WINDOW_KERNEL_SHAPES))
def test_window_kernel_shapes_compile(shape, one_chip, compiled_kernels):
    from unicore_tpu.ops.pallas import paged_attention as pa

    (rows, cells, num_pages, table, window), limit_mb = (
        WINDOW_KERNEL_SHAPES[shape])
    heads, d = 8, 128
    pp = pa.pick_pages_per_block(table, 64, d, num_heads=heads, itemsize=4)
    assert pp == 4
    limit = pa.vmem_limit_bytes(cells, heads * d, heads, pp * 64, 4, 4,
                                three_pass_slabs=heads)
    assert round(limit / 2 ** 20, 2) == limit_mb
    fn = functools.partial(
        pa.ragged_paged_attention, page_size=64, scale=d ** -0.5,
        window=window, three_pass=True)   # this model's step's rule
    pool = ((num_pages * 64, heads * d), F32)
    text = _compile(fn, one_chip, ((rows, cells, heads, d), F32), pool, pool,
                    ((rows, table), I32), ((rows, cells), I32),
                    ((rows,), I32))
    assert text.count("tpu_custom_call") == 1
