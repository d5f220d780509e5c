"""The ``pangu_moe_lm`` family through the harness at a toy width on the
CPU: the cell ADDED to the tests' benchmark root as new files and entries,
a whole closed-loop run with prefix hits on latent pages, the same run
with pieces of the model's mathematics left out in turn (the chip's
controls, PERF.md section 6, leave out more), the bfloat16 control, and
the new readers and counts on inputs whose answers can be worked out by
hand."""

import hashlib
import json
import os
import shutil
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from benchmarks import run as bench_run
from benchmarks.kernels import latent_attention as counts
from benchmarks.lib import (check, latent_readers, moe_readers, serve_cell,
                            spec, weights)
from benchmarks.lib.trace import Trace

CELL, CONFIG, MIX, LIKE = ("tiny_pangu_shareddocs", "tiny_pangu",
                           "tiny_pangu_shareddocs", "pangu_mla_shareddocs")


def make_root(tmp):
    """``bench_tiny``'s root with the toy cell added the way a PR adds
    one: new files plus entries, no file that was there edited."""
    root = bench_tiny.make_root(tmp)
    base = os.path.join(root, "benchmarks")
    before = bench_tiny._listing(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for kind, name in (("config", CONFIG), ("traffic", MIX),
                       ("workload", CELL)):
        shutil.copy(
            os.path.join(bench_tiny.TINY, f"{kind}_{name}.json"),
            os.path.join(base, kind + ("" if kind == "traffic" else "s"),
                         name + ".json"))
    bench["configs"].append({
        "name": CONFIG, "source": "tests", "reduced": [], "why": "tests",
        "file": f"benchmarks/configs/{CONFIG}.json"})
    bench["workloads"].append({"name": CELL, "config": CONFIG,
                               "traffic": MIX, "chips": 1, "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = bench_tiny._listing(root)
    changed = [p for p in before if p != "BENCHMARK.json"
               and before[p] != after.get(p)]
    assert not changed, f"adding the cell edited {changed}"
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def _run(root, tmp_path, seed=3_000_000_019, seconds=3.0):
    cell = spec.load_cell(CELL, root)
    return bench_run.run_cell(
        cell, seed, seconds, False, jax.devices()[:1], peaks=None,
        workdir=os.path.join(str(tmp_path), "work"),
        process_t0=time.perf_counter())


def _pangu_metrics():
    return [m for m in spec.load_benchmark()["per_layer"]
            if LIKE in m.get("workloads", ())]


def test_the_cell_loads_with_its_family_and_its_fourteen_metrics(root):
    cell = spec.load_cell(CELL, root)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        m["name"] for m in _pangu_metrics()}
    assert len(cell["per_layer"]) == 14
    real = spec.load_cell(LIKE)
    assert real["family"].dims(real["config"]) == {
        "layers": 5, "heads": 128, "head_dim": 192, "latent": 512,
        "rope": 64, "lanes": 640, "nope": 128, "v": 128, "hidden": 7680,
        "expert_layers": 4, "experts": 256, "experts_held": 8,
        "experts_per_token": 8, "expert_width": 2048}
    dims = cell["family"].dims(cell["config"])
    assert (dims["layers"], dims["expert_layers"], dims["heads"]) == (3, 2, 4)
    assert (dims["experts"], dims["experts_held"]) == (8, 4)


def test_the_configuration_holds_every_published_number():
    """The catalog's ``config`` under the same keys; what differs is
    listed in ``reduced`` (``BENCHMARK.json`` and the file agree), and no
    width is among it."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7680,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 25600000,
        "routed_scaling_factor": 2.5, "sandwich_norm": True,
        "tie_word_embeddings": False, "v_head_dim": 128,
        "vocab_size": 153600}
    cell = spec.load_cell(LIKE)
    cfg, entry = cell["config"], cell["config_entry"]
    differs = {k for k, v in published.items() if cfg[k] != v}
    assert differs == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers", "max_position_embeddings"}
    assert differs | {"torch_dtype"} == set(entry["reduced"])
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in entry["reduced"])
    # the floors: four expert layers behind the leading dense one, 8
    # routed experts held, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert (cfg["n_routed_experts"], cfg["router_outputs"]) == (8, 256)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert "32 chips share each expert layer" in cfg["deployment"]
    traffic = cell["traffic"]
    assert (traffic["kind"], traffic["clients"],
            traffic["asks_per_document"], traffic["ramp_s"]) == (
        "closed_loop", 8, 4, 12)
    assert [traffic[k] for k in ("document", "question", "answer")] == [
        {"dist": "uniform", "min": 4096, "max": 8192},
        {"dist": "uniform", "min": 16, "max": 64},
        {"dist": "uniform", "min": 16, "max": 64}]
    # every compared sequence fits the positions, and check.py's padding
    # to quarters of them keeps to three lengths
    assert 8192 + 64 + 64 <= cfg["max_position_embeddings"] == 4 * 2112


def test_the_weights_are_the_reckoned_13_64_gb():
    cell = spec.load_cell(LIKE)
    abstract = serve_cell.abstract_params(
        cell["family"].build_model(cell["config"]))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(abstract))
    assert n == 3_409_190_400 and round(n * 4 / 1e9, 2) == 13.64


def test_a_sound_run_is_correct_and_reports_the_cells_metrics(
        root, tmp_path):
    res = _run(root, tmp_path)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def _rope_key_left_out(monkeypatch):
    from unicore_tpu.serve import attention

    real = attention.write_latent_and_attend

    def without(q, entry, pages, paged, positions, scale, value_lanes):
        return real(q.at[..., value_lanes:].set(0.0), entry, pages, paged,
                    positions, scale, value_lanes)

    monkeypatch.setattr(attention, "write_latent_and_attend", without)


def _norm_skipped(*names):
    def patch(monkeypatch):
        from unicore_tpu.modules import pattern_decoder

        real = pattern_decoder.RMSNorm.__call__

        def skipping(self, x):
            # the parameter is still made: the trees agree
            out = real(self, x)
            return x if self.name in names else out

        monkeypatch.setattr(pattern_decoder.RMSNorm, "__call__", skipping)
    return patch


def _shared_expert_left_out(monkeypatch):
    from unicore_tpu.modules import pattern_decoder

    real = pattern_decoder.GatedFFN.__call__

    def without(self, x):
        out = real(self, x)
        return out * 0 if self.name == "shared_experts" else out

    monkeypatch.setattr(pattern_decoder.GatedFFN, "__call__", without)


def _another_documents_pages(monkeypatch):
    from unicore_tpu.serve import kv_pool

    monkeypatch.setattr(kv_pool, "_page_digest",
                        lambda digest, toks: hashlib.sha1(digest).digest())


LEFT_OUT = {
    "the rope key left out of the scores": _rope_key_left_out,
    "the latents cached un-normed": _norm_skipped("kv_a_layernorm"),
    "the shared expert left out": _shared_expert_left_out,
    "the two post-norms left out": _norm_skipped(
        "post_attention_layernorm", "post_mlp_layernorm"),
    "a prefix hit served from another document's pages":
        _another_documents_pages,
}


@pytest.mark.parametrize("what", sorted(LEFT_OUT))
def test_a_run_that_leaves_part_of_the_mathematics_out_is_not_correct(
        what, root, tmp_path, monkeypatch):
    LEFT_OUT[what](monkeypatch)
    res = _run(root, tmp_path)
    assert res["correct"] is False
    assert res["failed"] == 0  # every request finished: only `correct` sees it


@pytest.mark.parametrize("seed", [1, 2])
def test_the_bf16_control_fails_the_cells_limits(root, seed):
    cell = spec.load_cell(CELL, root)
    cfg, limits = cell["config"], cell["workload"]["limits"]
    model = cell["family"].build_model(cfg)
    params = weights.make(serve_cell.abstract_params(model), seed,
                          scales=cfg.get("weight_scales"))
    rng = np.random.default_rng(seed)
    sample = [types.SimpleNamespace(
        spec={"prompt": rng.integers(4, cfg["vocab_size"], 2).tolist()},
        seq=types.SimpleNamespace(
            generated=rng.integers(4, cfg["vocab_size"], 120).tolist()))
        for _ in range(12)]
    _, lower = check.serve_gaps(params, cell, sample)
    assert len(lower) == 12 * 120
    control = check.serve_numbers(lower, lower)
    assert control["moved"] > 0
    assert control["gap_share_of_bf16"] == 1.0 > limits["gap_share_of_bf16"]


def test_the_run_hits_the_prefix_cache_on_latent_pages(root, tmp_path):
    """Asks 2-4 of a document start past its shared pages: the window's
    prefix counter moves, which is what ``prefix_hit_token_pct.pangu``
    reads."""
    cell = spec.load_cell(CELL, root)
    engine, params, widths = serve_cell.build(cell, 5)
    assert not engine.prefix_cache_refused
    facts = serve_cell.drive(cell, engine, params, widths, 5, 2.0, False,
                             jax.devices()[:1], str(tmp_path),
                             time.perf_counter())
    saved = (facts["prefix_close"]["tokens_saved"]
             - facts["prefix_open"]["tokens_saved"])
    assert saved > 0
    assert facts["stats_close"]["latent_prefill_tokens"] > 0
    assert facts["stats_close"]["latent_decode_tokens"] > 0
    assert 0 < facts["stats_close"]["moe_assignments_held"] < facts[
        "stats_close"]["moe_assignments"]
    assert facts["stats_close"]["cache_bytes_per_token"] == 3 * 128 * 4


def test_the_latent_attentions_operations_and_bytes_by_hand():
    # the published widths: 128 heads, 128 + 64 a query, 128 a value, a
    # cache entry of 512 + 64 numbers, float32 cache and activations
    args = (128, 128, 64, 128)
    # one decode row over 6,000 entries: 6,000 pairs
    assert counts.pairs(1, 6000) == 6000
    assert counts.flops([(1, 6000)], *args) == 2 * 6000 * 128 * 320
    assert counts.bytes_moved([(1, 6000)], 128, 512, 128, 64, 128, 4, 4) == (
        6000 * 576 * 4 + 128 * 320 * 4)
    # a chunk of 32 tokens ending at 4,128: token i sees 4,096 + i + 1
    pairs = sum(4096 + i + 1 for i in range(32))
    assert counts.pairs(32, 4128) == pairs == 32 * 4128 - 32 * 31 / 2
    assert counts.flops([(32, 4128)], *args) == 2 * pairs * 128 * 320
    # the context's entries ONCE, whatever the chunk
    assert counts.bytes_moved([(32, 4128)], 128, 512, 128, 64, 128, 4, 4) == (
        4128 * 576 * 4 + 32 * 128 * 320 * 4)
    # rows add; nothing served is nothing
    assert counts.flops([(1, 6000), (32, 4128)], *args) == (
        counts.flops([(1, 6000)], *args) + counts.flops([(32, 4128)], *args))
    assert counts.flops([], *args) == 0
    assert counts.bytes_moved([], 128, 512, 128, 64, 128, 4, 4) == 0
    # a lower bound for either form: the absorbed form's 2 x 128 x (576 +
    # 512) a pair is 3.4 times it
    assert 2 * 128 * (576 + 512) / (2 * 128 * 320) == 3.4


PLANE = "/device:TPU:0"
STEPS = [(0.0, 0.5, 1), (0.5, 0.5, 16)]


def _ctx(root, ops, host=(), signatures=None,
         rows=(((1, 40),), ((16, 16), (1, 33))), steps=STEPS):
    cell = spec.load_cell(CELL, root)
    return {"trace": Trace({PLANE: list(ops)}, list(host), signatures),
            "planes": [PLANE], "t0": 0, "t1": 1000, "window_s": 1e-6,
            "busy_s": 800e-9, "cell": cell, "rows": [list(r) for r in rows],
            "steps": list(steps), "pool_itemsize": 4,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


def test_the_latent_readers_on_a_small_trace_by_hand(root):
    ops = [("fusion.1", 0, 100), ("ragged_paged_attention.1", 100, 200),
           ("fusion.5", 300, 50), ("fusion.6", 350, 30), ("copy.7", 380, 20),
           ("ragged_paged_attention.2", 500, 100), ("fusion.9", 600, 70)]
    sig = {"fusion.1": "fusion.1 fusion f32[1,20,64]{2,1,0}",
           # the absorption into the queries [tokens, heads, latent] and
           # out of the outputs [tokens, heads, v], the cells' re-layout
           # [tokens, heads, lanes]
           "fusion.5": "fusion.5 fusion f32[1,20,4,32]{3,2,1,0}",
           "fusion.6": "fusion.6 fusion f32[20,4,16]{2,1,0}",
           "copy.7": "copy.7 copy f32[20,4,128]{2,1,0}",
           # a decode step's kernel call has the cells' own shape [rows,
           # heads, lanes]: found as the kernel, not a second time
           "ragged_paged_attention.2": "ragged_paged_attention.2 "
                                       "custom-call f32[4,4,128]{2,1,0}",
           # not the latent attention's: a head's worth of something else
           "fusion.9": "fusion.9 fusion f32[20,4,24]{2,1,0}"}
    ctx = _ctx(root, ops, signatures=sig)
    # toy sizes: 4 heads, 16 + 8 a query, 16 a value, 32 + 8 an entry,
    # three layers; memory-bound at these peaks
    least = 0.0
    for rows in ctx["rows"]:
        f = counts.flops(rows, 4, 16, 8, 16)
        b = counts.bytes_moved(rows, 4, 32, 16, 8, 16, 4, 4)
        least += 3 * max(f / 1e12, b / 1e9)
    # the two kernel events and the three absorption events: 400 ns
    assert latent_readers.attn_roofline_pct(ctx) == pytest.approx(
        100.0 * least / 400e-9)
    assert latent_readers.attn_device_pct(ctx) == pytest.approx(
        100.0 * 400 / 800)


def test_the_patterns_take_their_sizes_from_the_configuration(root):
    tiny, real = spec.load_cell(CELL, root), spec.load_cell(LIKE)
    assert set(real["workload"]["kernel_events"]) == set(
        tiny["workload"]["kernel_events"])
    assert real["workload"]["kernel_events"]["latent_attention"] == (
        'custom_call_target="tpu_custom_call"')
    assert real["workload"]["expect_dispatch"] == {
        "latent_attention_decode": "pallas",
        "latent_attention_prefill": "pallas", "moe_experts": "reference"}
    absorb = latent_readers.event_pattern(real, "latent_absorb")
    for result in ("copy f32[512,128,640]{2,1,0:T(8,128)}",
                   "slice f32[16,64,128,512]{3,2,1,0:T(8,128)}",
                   "fusion f32[512,128,512]{2,1,0}",
                   "fusion f32[1,16,128,128]{3,2,1,0}",
                   "fusion f32[512,128,640]{0,2,1:T(8,128)}"):
        assert absorb.search(f"op.3 {result}")
    # not the projection, an expert's rows, or the kernel's own call
    for result in ("fusion f32[1,512,128,192]{2,1,3,0}",
                   "fusion f32[4096,7680]{1,0}",
                   "custom-call f32[16,128,640]{2,1,0}",
                   "custom-call f32[256,512,640]{2,1,0}"):
        assert not absorb.search(f"op.3 {result}")
    experts = moe_readers.event_pattern(real, "moe_experts")
    # the loop's three fusions at this cell's block rows (8 and 32), and
    # not the shared expert's gate and up results on the whole list
    for result in ("f32[8,2048]{1,0:T(8,128)S(1)}", "f32[32,2048]{1,0}",
                   "f32[136,32,7680]{2,1,0}", "f32[23,8,7680]{2,1,0}"):
        assert experts.search(f"fusion.540 fusion {result}")
    for result in ("f32[512,2048]{1,0}", "f32[16,2048]{1,0}",
                   "f32[1,512,2048]{2,1,0}", "f32[512,7680]{1,0}"):
        assert not experts.search(f"fusion.413 fusion {result}")
    assert latent_readers.event_pattern(real, "flash_attention") is None


@pytest.mark.parametrize("metric", _pangu_metrics(), ids=lambda m: m["name"])
def test_each_reader_of_the_cell_through_the_loader(metric, root,
                                                    monkeypatch):
    """Every per-layer metric of the cell has a reader file; one that
    reads PR 24's spans gives what its ``.docs`` or ``.chat`` sibling gives
    on the same trace; and none raises on a program that records nothing."""
    from test_bench_span_readers import HOST, NESTED, ctx_of
    from unicore_tpu.ops import moe

    monkeypatch.setattr(moe, "routing_report", lambda: [(8, 6), (128, 8)])
    base = os.path.join(spec.ROOT, "benchmarks")
    read = spec.load_reader(metric["name"], base)
    assert metric["moves"] == "serve_tokens_per_s"
    assert metric["workloads"] == [LIKE]
    stem = metric["name"].replace("-pangu", "").replace(".pangu", "")
    spans = ctx_of(HOST + NESTED)
    if metric["source"] == "program_span":
        like = ".chat" if stem == "serve_decode_dispatch_ms" else ".docs"
        assert read(spans) == spec.load_reader(stem + like, base)(spans)
    if metric["source"] not in ("host_clock", "program_counter"):
        bare = _ctx(root, [("fusion.1", 0, 100)], NESTED)
        if stem == "device_idle_pct":    # 800 of the 1000 ns were busy
            assert read(bare) == pytest.approx(20.0)
        else:
            assert read(bare) is None
    if stem == "prefix_hit_token_pct":
        assert read({"prompt_tokens": 400, "prefix_tokens_saved": 300}) == 75.0
        assert read({"prompt_tokens": 0, "prefix_tokens_saved": 0}) is None


BROKEN = {
    "no window bounds": lambda c: [c.pop("t0"), c.pop("t1")],
    "no trace at all": lambda c: c.pop("trace"),
    "no cell": lambda c: c.pop("cell"),
}


@pytest.mark.parametrize("how", sorted(BROKEN))
@pytest.mark.parametrize("reader", [
    latent_readers.attn_roofline_pct, latent_readers.attn_device_pct],
    ids=lambda r: r.__name__)
def test_a_context_a_reader_cannot_read_is_nothing_to_read(
        reader, how, root, capsys):
    ops = [("ragged_paged_attention.1", 500, 300)]
    ctx = _ctx(root, ops)
    BROKEN[how](ctx)
    assert reader(ctx) is None
    assert "Error" in "".join(capsys.readouterr())


def test_a_family_without_a_latent_gives_nothing_to_read():
    """On a cell of another family (the parent's programs, laid under
    this PR's benchmark files) the latent readers find nothing."""
    cell = spec.load_cell("lfm2_moe_longgen")
    ctx = {"trace": Trace({PLANE: [("ragged_paged_attention.1", 0, 100)]},
                          [], None),
           "planes": [PLANE], "t0": 0, "t1": 1000, "window_s": 1e-6,
           "busy_s": 800e-9, "cell": cell, "rows": [[(1, 40)]],
           "steps": [(0.0, 0.5, 1)], "pool_itemsize": 4,
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}
    assert latent_readers.attn_roofline_pct(ctx) is None
    assert latent_readers.attn_device_pct(ctx) is None
