"""One way to pick a kernel: each op chooses from shape, and from nothing
else but the backend.

Three things are held here.

- *The choices the benchmark's cells run on, pinned as literals.*  The
  shapes are read from ``benchmarks/configs/*.json`` and the cells'
  traffic; the expected values are what the tree returned when the shape
  rules became the only dispatch (PR 29: the same as the commit before
  it).  A kernel ``perf_opt`` that moves a block size or a crossover
  changes the literal on purpose, with a cell on each side of it.
- *What a rule must promise for every shape it admits*: blocks that
  divide the lengths and meet Mosaic's tiling, a page block inside the
  table and the scratch budget.
- *The choice reads nothing else*: no file under ``~/.cache``, no
  environment variable.
"""

import functools
import itertools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from unicore_tpu import ops
from unicore_tpu.modules import multihead_attention as mha
from unicore_tpu.ops import backend
from unicore_tpu.ops import fused_cross_entropy as fce
from unicore_tpu.ops import rounding
from unicore_tpu.ops.pallas import flash_attention as fa
from unicore_tpu.ops.pallas import paged_attention as pa
from unicore_tpu.ops.pallas import softmax_dropout as pl_sd
from unicore_tpu.ops.softmax_dropout import (
    _heuristic_kernel_win, _pallas_eligible,
)
from unicore_tpu.serve import attention as serve_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SDS = jax.ShapeDtypeStruct
BF16, F32 = jnp.bfloat16, jnp.float32


def _bench_json(kind, name):
    with open(os.path.join(REPO, "benchmarks", kind, name + ".json")) as f:
        return json.load(f)


_config = functools.partial(_bench_json, "configs")
_traffic = functools.partial(_bench_json, "traffic")


@pytest.fixture
def on_chip(monkeypatch):
    """The auto backend as the chip sees it: ``use_pallas()`` true and
    kernels compiled, not interpreted.  Nothing is lowered under it —
    the tests trace (``jax.make_jaxpr``) or call the rules directly."""
    from unicore_tpu import parallel

    monkeypatch.setattr(backend, "_on_tpu",
                        functools.lru_cache(None)(lambda: True))
    monkeypatch.setattr(backend, "_BACKEND", "auto")
    # one chip: no mesh a trainer test of this process may have left
    monkeypatch.setattr(backend, "_SPMD_MESH", None)
    monkeypatch.setitem(parallel._TENSOR_PARALLEL, "mesh", None)


def _path(op, desc):
    return backend.dispatch_report()[op][desc]


# -- the cells' choices, pinned --------------------------------------------


def _bert_attention_shapes():
    cfg, traffic = _config("bert_base"), _traffic("mlm_b64_s512")
    b, t = traffic["batch_per_chip"], traffic["seq_len"]
    h = cfg["num_attention_heads"]
    q = SDS((b, t, h, cfg["hidden_size"] // h), BF16)
    # the encoder casts its [1, H, T, T] relative-position bias to the
    # compute dtype; the rows are padded (256-511 real of 512)
    return q, SDS((1, h, t, t), BF16)


@pytest.mark.parametrize("forced,wins", [
    ("auto", True), ("pallas", True), ("reference", False)])
def test_bert_base_attention_takes_flash(on_chip, forced, wins):
    q, bias = _bert_attention_shapes()
    assert q.shape == (64, 512, 12, 64) and bias.shape == (1, 12, 512, 512)
    with backend.kernel_backend(forced):
        assert mha._flash_wins(q, q, bias) is wins
        assert mha._flash_ok(q, q, bias, True, False) is wins
    desc = ("q(64, 512, 12, 64) k512 bfloat16 bias=(1, 12, 512, 512) "
            "pad=True causal=False dropout=False")
    assert _path("flash_attention", desc) == (
        "pallas" if wins else "reference")


def test_bert_base_flash_blocks():
    """One (512, 512) block: the single-block forward and the joint
    one-pass backward, which is why the bias rule lets it through."""
    q, bias = _bert_attention_shapes()
    t = q.shape[1]
    assert fa.picked_blocks(t, t, bias.shape, bias.dtype) == (512, 512)
    assert fa.picked_blocks(t, t) == (512, 512)


def test_bert_base_fused_ce_chunk():
    cfg, traffic = _config("bert_base"), _traffic("mlm_b64_s512")
    # the LM head projects 25% of the positions (static slots)
    rows = traffic["batch_per_chip"] * traffic["seq_len"] // 4
    assert (rows, cfg["vocab_size"]) == (8192, 30522)
    assert fce._resolve_chunk(rows, cfg["vocab_size"]) == 256


@pytest.mark.parametrize("rows,vocab,chunk", [
    (64, 30522, None),      # 7.8 MB of fp32 logits: under FUSE_MIN_BYTES
    (256, 8192, None),
    (32768, 30522, 256),
    (4096, 50272, 128),
    (128, 50272, None),     # one chunk would hold every row: unfused
    (128, 1 << 20, 16),     # MIN_CHUNK
])
def test_fused_ce_byte_rule(rows, vocab, chunk):
    assert fce._resolve_chunk(rows, vocab) == chunk


def _serve_shapes(name, width):
    """What ``write_and_attend`` hands the kernel: under grouped queries
    the K/V heads, and the ``g`` query heads of a group as ``g`` query
    cells a position (``serve/attention.py``)."""
    cfg = _config(name)
    eng = cfg["engine"]
    h = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", h)  # opt_1.3b has no such key
    d = cfg["hidden_size"] // h
    table = cfg["max_position_embeddings"] // eng["page_size"]
    q = SDS((eng["max_batch"], width * (h // kv), kv, d), F32)
    pool = SDS((eng["num_pages"] * eng["page_size"], kv * d), F32)
    return q, pool, SDS((eng["max_batch"], table), jnp.int32), eng


@pytest.mark.parametrize("name,width,geometry", [
    ("opt_1.3b", 1, (32, 32, 64, 32)),
    ("opt_1.3b", 128, (32, 32, 64, 32)),
    ("olmo_hybrid_7b", 1, (12, 30, 128, 128)),
    ("olmo_hybrid_7b", 64, (12, 30, 128, 128)),
    # 32 query heads over 8 K/V heads: the kernel sees the 8, and four
    # query cells a position (4 and 512 to a row)
    ("lfm2_24b_a2b", 1, (32, 8, 64, 32)),
    ("lfm2_24b_a2b", 128, (32, 8, 64, 32)),
])
def test_serve_cells_pages_per_block(on_chip, name, width, geometry):
    """Both widths of the serve configurations: the compiled kernel
    supports the shape, and 4 pages of 64 (256 slots) go into a block,
    with two K/V slots as with one (``tests/test_chip_compile.py`` pins
    the VMEM limit that pays for the second)."""
    q, pool, table, eng = _serve_shapes(name, width)
    assert (q.shape[0], q.shape[2], q.shape[3], table.shape[1]) == geometry
    assert width in (1, eng["prefill_chunk"])
    assert q.shape[1] * q.shape[2] == width * _config(name)[
        "num_attention_heads"]
    assert pa.supported(q.shape[2], q.shape[3], eng["page_size"], 4)
    assert serve_attention._kernel_ok(q, pool, table, eng["page_size"]) == 4
    with backend.kernel_backend("reference"):
        assert serve_attention._kernel_ok(
            q, pool, table, eng["page_size"]) is None


@pytest.mark.parametrize("width,rows,cells", [
    (1, 16, 128),        # a decode row: one token's 128 heads
    (64, 256, 512),      # a chunk of 64 in 16 tiles of 4 tokens x 128 heads
])
def test_the_latent_cells_kernel_choices(on_chip, width, rows, cells):
    """``pangu_mla_shareddocs``: both widths hand the ragged kernel ONE
    K/V head as wide as a latent page (512 + 64 + 64 lanes), the heads of
    a token as query cells, 512 cells a kernel row at most; the shape is
    supported, 4 pages of 64 go into a block, and a share of 8 of 256
    experts sizes its blocks from the choices it can expect."""
    from unicore_tpu.modules.pattern_decoder import LATENT_LANES
    from unicore_tpu.ops import moe

    cfg = _config("openpangu_ultra_moe_718b")
    eng = cfg["engine"]
    heads = cfg["num_attention_heads"]
    lanes = -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
              // LATENT_LANES) * LATENT_LANES
    assert lanes == 640 and width in (1, eng["prefill_chunk"])
    tokens = min(width, serve_attention.LATENT_QUERY_CELLS // heads)
    assert (eng["max_batch"] * width // tokens, tokens * heads) == (
        rows, cells)
    q = SDS((rows, cells, 1, lanes), F32)
    pool = SDS((eng["num_pages"] * eng["page_size"], lanes), F32)
    table = SDS((rows, cfg["max_position_embeddings"] // eng["page_size"]),
                jnp.int32)
    assert pa.supported(1, lanes, eng["page_size"], 4)
    assert serve_attention._kernel_ok(q, pool, table, eng["page_size"]) == 4
    with backend.kernel_backend("reference"):
        assert serve_attention._kernel_ok(
            q, pool, table, eng["page_size"]) is None
    held, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    step_tokens = eng["max_batch"] if width == 1 else 512
    expected = -(-step_tokens * k * held // cfg["router_outputs"])
    assert moe.pick_block_rows(expected, held) == (8 if width == 1 else 32)


@pytest.mark.parametrize("width", [1, 128])
@pytest.mark.parametrize("kind,heads,table_pages", [
    ("full_attention", 48, 264), ("sliding_attention", 64, 11)])
def test_the_window_cells_kernel_choices(on_chip, kind, heads, table_pages,
                                         width):
    """``laguna_swa_mixedlen``: layers of two kinds hand the ragged kernel
    the same 8 K/V heads of 128 with 6 or 8 query cells a position (768 /
    1,024 to a row of a mixed step), a global layer over the 264 pages of
    the context and a sliding one over a row's 11 window pages; both take
    4 pages of 64 a block.  A share of 64 of 256 experts of width 512
    sizes its blocks from the choices it can expect: 8 rows for a decode
    step's 32 tokens, 32 for a mixed step's 512."""
    from unicore_tpu.ops import moe
    from unicore_tpu.serve.kv_pool import PagedKVPool

    cfg = _config("laguna_xs2")
    eng, kv, d = cfg["engine"], cfg["num_key_value_heads"], cfg["head_dim"]
    assert heads in cfg["num_attention_heads_per_layer"] and kind in cfg[
        "layer_types"]
    assert width in (1, eng["prefill_chunk"]) and (kv, d) == (8, 128)
    window = cfg["sliding_window"] if kind == "sliding_attention" else 0
    pool = PagedKVPool(eng["num_pages"], eng["page_size"],
                       prefix_cache=False, window=cfg["sliding_window"],
                       num_window_pages=329, window_slack=8)
    assert pool.window_reserve == 10
    assert 1 + eng["max_batch"] * pool.window_reserve + 8 == 329
    pages = (pool.window_row_pages(eng["prefill_chunk"]) if window
             else cfg["max_position_embeddings"] // eng["page_size"])
    assert pages == table_pages
    q = SDS((eng["max_batch"], width * (heads // kv), kv, d), F32)
    assert q.shape[1] == width * (6 if heads == 48 else 8)
    slots = (329 if window else eng["num_pages"]) * eng["page_size"]
    table = SDS((eng["max_batch"], pages), jnp.int32)
    assert pa.supported(kv, d, eng["page_size"], 4)
    assert serve_attention._kernel_ok(q, SDS((slots, kv * d), F32), table,
                                      eng["page_size"]) == 4
    held, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    assert (held, cfg["router_outputs"], cfg["moe_intermediate_size"]) == (
        64, 256, 512)
    step_tokens = eng["max_batch"] if width == 1 else 512
    expected = -(-step_tokens * k * held // cfg["router_outputs"])
    assert moe.pick_block_rows(expected, held) == (8 if width == 1 else 32)


SD_SHAPES = {
    # name: (x, mask, bias), all bf16
    "bert_base": ((64, 12, 512, 512), None, (1, 12, 512, 512)),
    "evoformer_5d": ((1, 128, 4, 128, 128), (1, 128, 1, 1, 128),
                     (1, 1, 4, 128, 128)),
    "unimol_pair": ((32, 64, 256, 256), None, (32, 64, 256, 256)),
    "long_k": ((4, 8, 1024, 2048), None, (1, 8, 1024, 2048)),
}


def _sd_operands(name):
    sds = lambda s: None if s is None else SDS(s, BF16)  # noqa: E731
    return tuple(sds(s) for s in SD_SHAPES[name])


def _holds_kernel(fn, *shapes):
    """Trace ``fn`` anew (a fresh lambda: no cached trace stands in for
    the dispatch) and say whether the program holds a Pallas kernel."""
    return "pallas_call" in str(jax.make_jaxpr(lambda *a: fn(*a))(*shapes))


def _trace_softmax_dropout(x, mask, bias):
    """Whether the traced op holds the kernel, checked against what the
    dispatch site recorded (it records only where the backend lets the
    kernel be asked for at all)."""
    present = {k: v for k, v in (("mask", mask), ("bias", bias))
               if v is not None}
    kernel = _holds_kernel(
        lambda x_, kw: ops.softmax_dropout(x_, 0.0, is_training=False, **kw),
        x, present)
    if backend.use_pallas():
        desc = "x%s bfloat16 mask=%s bias=%s dropout=False" % (
            x.shape, None if mask is None else mask.shape,
            None if bias is None else bias.shape)
        assert _path("softmax_dropout", desc) == (
            "pallas" if kernel else "reference")
    return kernel


@pytest.mark.parametrize("name,q_blk,auto", [
    ("bert_base", 256, "pallas"),
    # 128 x 128 = 16K elements a program, under the 64K gate: the
    # reference under auto, the kernel only when forced
    ("evoformer_5d", 128, "reference"),
    ("unimol_pair", 256, "pallas"),
    ("long_k", 128, "pallas"),
])
def test_softmax_dropout_choice(on_chip, name, q_blk, auto):
    x, mask, bias = _sd_operands(name)
    assert _pallas_eligible(x, mask, bias)
    assert pl_sd._pick_q_blk_for(x, mask, bias) == q_blk
    assert _heuristic_kernel_win(x, mask, bias) is (auto == "pallas")
    assert _trace_softmax_dropout(x, mask, bias) is (auto == "pallas")
    with backend.kernel_backend("pallas"):
        assert _trace_softmax_dropout(x, mask, bias)
    with backend.kernel_backend("reference"):
        assert not _trace_softmax_dropout(x, mask, bias)


def test_dispatch_report_names_the_path_taken(on_chip):
    """There is no compile probe behind a dispatch site: the path taken
    is the path compiled, and ``dispatch_report`` names it — the kernel
    under a forced backend, the reference where the gate says the rows
    of a program are too few."""
    x = SDS((2, 512, 128), F32)
    desc = "x(2, 512, 128) float32 mask=None bias=None dropout=False"

    def path():
        _holds_kernel(
            lambda x_: ops.softmax_dropout(x_, 0.0, is_training=False), x)
        return _path("softmax_dropout", desc)

    with backend.kernel_backend("pallas"):
        assert path() == "pallas"
    # the report is a copy: editing it does not edit the record
    backend.dispatch_report()["softmax_dropout"][desc] = "edited"
    assert _path("softmax_dropout", desc) == "pallas"
    assert path() == "reference"  # 256 x 128 elements a program


@pytest.mark.parametrize("where,path", [
    ("chip", "pallas"), ("cpu", "reference"), ("mesh", "reference")])
def test_sr_cast_choice(monkeypatch, request, where, path):
    """A BERT-base moment (768 x 768): the kernel on one chip, the
    threefry reference off it and under a multi-device mesh."""
    if where != "cpu":
        request.getfixturevalue("on_chip")
    if where == "mesh":
        monkeypatch.setattr(backend, "_SPMD_MESH", object())
    n = _config("bert_base")["hidden_size"] ** 2
    kernel = _holds_kernel(rounding.fp32_to_bf16_sr, SDS((n,), F32),
                           jax.random.PRNGKey(0))
    assert kernel is (path == "pallas")
    assert _path("fp32_to_bf16_sr", "n%d" % n) == path


# -- what every rule promises, over a grid of shapes -----------------------

LENGTHS = (128, 256, 384, 512, 640, 768, 1024, 1152, 1536, 2048, 2560,
           3072, 4096)
BIAS_CLASSES = {
    "no_bias": lambda tq, tk: (None, None),
    "full_bf16": lambda tq, tk: ((1, 8, tq, tk), BF16),
    "full_f32": lambda tq, tk: ((1, 8, tq, tk), F32),
    "row_broadcast": lambda tq, tk: ((1, 8, 1, tk), BF16),
}


@pytest.mark.parametrize("bias_class", sorted(BIAS_CLASSES))
@pytest.mark.parametrize("d", [32, 64, 96, 128])
def test_picked_blocks_divide_and_tile(d, bias_class):
    """Whenever ``eligible`` says yes, the blocks divide the lengths,
    meet Mosaic's tiling (sublanes of 8, lanes of 128) and keep the
    fp32 score block inside its 4 MB."""
    seen = 0
    for tq, tk in itertools.product(LENGTHS, LENGTHS):
        bias_shape, bias_dtype = BIAS_CLASSES[bias_class](tq, tk)
        if not fa.eligible((2, 8, tq, d), (2, 8, tk, d), bias_shape):
            continue
        seen += 1
        bq, bk = fa.picked_blocks(tq, tk, bias_shape, bias_dtype)
        assert tq % bq == 0 and tk % bk == 0, (tq, tk, bq, bk)
        assert bq % 8 == 0 and bk % 128 == 0, (tq, tk, bq, bk)
        assert bq * bk * 4 <= 4 << 20, (tq, tk, bq, bk)
        # one authority: the kernel's own set-up reads the same pair
        q, k = SDS((2, 8, tq, d), BF16), SDS((2, 8, tk, d), BF16)
        bias = None if bias_shape is None else SDS(bias_shape, bias_dtype)
        assert fa._common(q, k, bias)[5:7] == (bq, bk)
    assert seen == len(LENGTHS) ** 2


@pytest.mark.parametrize("t", [100, 130, 520])
def test_flash_ineligible_lengths(t):
    assert not fa.eligible((2, 8, t, 64), (2, 8, t, 64), None)


@pytest.mark.parametrize("t,with_bias,without", [
    # single block, or k >= 1024: flash; a trainable bias in the
    # multi-block regime under 1024 pays its dbias sweep: einsum
    (384, True, True), (512, True, True), (640, False, True),
    (768, False, True), (1024, True, True), (4096, True, True)])
def test_flash_bias_crossover(on_chip, t, with_bias, without):
    q = SDS((4, t, 12, 64), BF16)
    assert mha._flash_wins(q, q, SDS((1, 12, t, t), BF16)) is with_bias
    assert mha._flash_wins(q, q, None) is without
    with backend.kernel_backend("pallas"):
        assert mha._flash_wins(q, q, SDS((1, 12, t, t), BF16))


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("operands", ["plain", "mask", "bias", "mask_bias"])
def test_softmax_row_block_divides_rows(dtype, operands):
    """The row block divides the row count and its double-buffered
    streams fit the 6 MB the kernel budgets (8 rows at the least)."""
    for q, k in itertools.product(
            (8, 64, 100, 128, 384, 500, 512, 1000, 1024),
            (128, 512, 2048, 8192)):
        x = SDS((2, 4, q, k), dtype)
        mask = SDS((2, 1, 1, k), dtype) if "mask" in operands else None
        bias = SDS((1, 4, q, k), dtype) if "bias" in operands else None
        blk = pl_sd._pick_q_blk_for(x, mask, bias)
        assert 1 <= blk <= q and q % blk == 0, (q, k, blk)
        streams = 3 + (mask is not None) + (bias is not None)
        stack = 2 * streams * blk * k * x.dtype.itemsize
        assert stack <= 6 << 20 or blk <= 8, (q, k, blk, stack)


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("heads,d", [(32, 64), (30, 128), (8, 128),
                                     (4, 8), (64, 128)])
def test_pages_per_block_within_table_and_budget(heads, d, itemsize):
    for table, page in itertools.product((1, 3, 32, 128, 512),
                                         (8, 16, 64, 128, 256)):
        pp = pa.pick_pages_per_block(table, page, d, num_heads=heads,
                                     itemsize=itemsize)
        assert 1 <= pp <= table, (table, page, pp)
        # the budget is ONE slot's keys and values; the second slot does
        # not shrink a block, the kernel's VMEM limit pays for it
        scratch = 2 * pp * page * heads * d * itemsize
        assert scratch <= pa._SCRATCH_BUDGET_BYTES or pp == 1
        assert pa.vmem_limit_bytes(1, heads * d, heads, pp * page,
                                   itemsize, itemsize) > pa.SLOTS * scratch
        # no more than the 256 slots it aims for, rounded up to a page
        assert (pp - 1) * page < 256


# -- the choice reads its arguments and the backend, nothing else ----------

_CHOICES = '''
import functools, json
import jax, jax.numpy as jnp
from unicore_tpu import ops
from unicore_tpu.ops import backend
from unicore_tpu.ops import fused_cross_entropy as fce
from unicore_tpu.ops import rounding
from unicore_tpu.serve import attention as serve_attention

backend._on_tpu = functools.lru_cache(None)(lambda: True)
S = jax.ShapeDtypeStruct
f32 = jnp.float32
ce = jax.make_jaxpr(
    lambda f, k, t: fce.fused_linear_cross_entropy(f, k, t, tied=True))(
    S((8192, 8), f32), S((30522, 8), f32), S((8192,), jnp.int32))
jax.eval_shape(rounding.fp32_to_bf16_sr, S((768 * 768,), f32),
               jax.random.PRNGKey(0))
jax.eval_shape(lambda x: ops.softmax_dropout(x, 0.0, is_training=False),
               S((2, 512, 128), f32))
print(json.dumps({
    "pages_per_block": serve_attention._kernel_ok(
        S((32, 1, 32, 64), f32), S((288 * 64, 2048), f32),
        S((32, 32), jnp.int32), 64),
    "ce_chunked": "scan" in str(ce),
    "dispatch": backend.dispatch_report(),
}, sort_keys=True))
'''

# what the deleted per-machine overlay looked like: one verdict against
# each choice above, under this environment's fingerprint
_OVERLAY = {
    "ragged_paged_attention|float32|32|1|32|64|64|32":
        {"pages_per_block": 1},
    "fused_ce|float32|8192|8|32768|1|0": "eager",
    "optim_sr_cast|float32|1048576": "eager",
    "softmax_dropout|float32|3|512|128|~|~|0": {"q_blk": 64},
}


def _choices(env):
    env = dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", _CHOICES], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_choice_reads_no_file_and_no_env(tmp_path):
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "none"
    fingerprint = "fmt1|cpu|jax%s|libtpu%s" % (jax.__version__, libtpu)
    cache_dir = tmp_path / ".cache" / "unicore_tpu"
    cache_dir.mkdir(parents=True)
    (cache_dir / "kernel_tune_cache.json").write_text(json.dumps({
        "format": 1,
        "entries": {fingerprint: {
            key: {"winner": winner, "source": "timed"}
            for key, winner in _OVERLAY.items()}},
    }))
    base = {k: v for k, v in os.environ.items()
            if not k.startswith("UNICORE_TPU_")}
    plain = _choices(base)
    steered = _choices(dict(
        base, HOME=str(tmp_path), UNICORE_TPU_CACHE_DIR=str(cache_dir),
        UNICORE_TPU_KERNEL_AUTOTUNE="tune"))
    assert plain == steered
    assert plain["pages_per_block"] == 4 and plain["ce_chunked"]
    assert plain["dispatch"] == {
        "fp32_to_bf16_sr": {"n589824": "pallas"},
        "softmax_dropout": {
            "x(2, 512, 128) float32 mask=None bias=None dropout=False":
                "reference"},
    }


# -- the engine's prefill chunk ---------------------------------------------


@pytest.mark.parametrize("asked,got", [(0, 32), (16, 16), (8, 8)])
def test_prefill_chunk_default_and_explicit(asked, got):
    from examples.lm.model import TransformerLMModel
    from unicore_tpu.serve.engine import DEFAULT_PREFILL_CHUNK, ServeEngine

    model = TransformerLMModel(
        vocab_size=29, padding_idx=0, decoder_layers=1,
        decoder_embed_dim=32, decoder_ffn_embed_dim=64,
        decoder_attention_heads=4, max_seq_len=64, emb_dropout=0.0,
        dropout=0.0, attention_dropout=0.0, activation_dropout=0.0,
        rel_pos=False, abs_pos=False, rotary=True,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = ServeEngine(model, params, num_pages=16, page_size=4,
                         max_batch=2, prefill_chunk=asked)
    assert DEFAULT_PREFILL_CHUNK == 32
    assert engine.prefill_chunk == got
    assert engine.serve_step_widths() == (1, got)
