"""The ``lfm2_moe_lm`` family through the harness at a toy width on the
CPU: the cell ADDED to the tests' benchmark root as new files and entries,
a whole closed-loop run, the same run with each piece of the model's
mathematics left out in turn (three experts a token, the bias on the
weights, the tails dropped between steps), the bfloat16 control, and the
new readers and counts on inputs whose answers can be worked out by
hand."""

import json
import os
import shutil
import time
import types

import jax
import numpy as np
import pytest

import bench_tiny
from benchmarks import run as bench_run
from benchmarks.kernels import moe_experts as expert_counts
from benchmarks.lib import check, moe_readers, serve_cell, spec, weights
from benchmarks.lib.trace import Trace

CELL, CONFIG, MIX, LIKE = ("tiny_lfm2_longgen", "tiny_lfm2",
                           "tiny_lfm2_longgen", "lfm2_moe_longgen")


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


def _lfm2_metrics():
    return [m for m in spec.load_benchmark()["per_layer"]
            if LIKE in m.get("workloads", ())]


def test_the_cell_loads_with_its_family_and_its_twelve_metrics(root):
    cell = spec.load_cell(CELL, root)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        m["name"] for m in _lfm2_metrics()}
    assert len(cell["per_layer"]) == 12
    dims = cell["family"].dims(cell["config"])
    # `layers` is what the ragged reader multiplies by: the layers that
    # hold pages; the experts sit in the layers behind the dense ones
    assert (dims["layers"], dims["conv_layers"], dims["expert_layers"]) == (
        1, 4, 4)
    assert (dims["heads"], dims["kv_heads"], dims["head_dim"]) == (4, 2, 16)
    assert (dims["experts"], dims["experts_per_token"],
            dims["expert_width"], dims["hidden"]) == (8, 2, 32, 64)
    real = spec.load_cell(LIKE)
    assert real["family"].dims(real["config"]) == {
        "layers": 1, "conv_layers": 4, "expert_layers": 4, "heads": 32,
        "kv_heads": 8, "head_dim": 64, "hidden": 2048, "experts": 64,
        "experts_per_token": 4, "expert_width": 1536, "conv_kernel": 3}


def test_the_configuration_holds_every_published_number():
    """The catalog's ``config`` under the same keys; what differs is
    listed in ``reduced`` (``BENCHMARK.json`` and the file agree)."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
        "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    cell = spec.load_cell(LIKE)
    cfg, entry = cell["config"], cell["config_entry"]
    differs = {k for k, v in published.items() if cfg[k] != v}
    assert differs | {"layer_types", "torch_dtype"} == set(entry["reduced"])
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert cfg["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                  "conv"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    traffic = cell["traffic"]
    assert (traffic["clients"], traffic["asks_per_document"],
            traffic["ramp_s"]) == (32, 1, 10)
    assert [traffic[k] for k in ("document", "question", "answer")] == [
        {"dist": "uniform", "min": 64, "max": 448},
        {"dist": "uniform", "min": 16, "max": 64},
        {"dist": "uniform", "min": 256, "max": 1024}]


def test_a_sound_run_is_correct_and_reports_the_cells_metrics(
        root, tmp_path):
    res = _run(root, tmp_path)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def _three_experts(monkeypatch):
    from unicore_tpu.ops import moe

    real = moe.route

    def three(scores, bias, top_k, scale=1.0):
        sel, w = real(scores, bias, top_k, scale)
        return sel, w.at[:, -1].set(0.0)  # the last choice gets nothing

    monkeypatch.setattr(moe, "route", three)


def _bias_on_the_weights(monkeypatch):
    from unicore_tpu.ops import moe

    def biased(scores, bias, top_k, scale=1.0):
        both = scores + bias
        w, sel = jax.lax.top_k(both, top_k)
        return sel, scale * w / (w.sum(-1, keepdims=True) + 1e-6)

    monkeypatch.setattr(moe, "route", biased)


def _tails_dropped(monkeypatch):
    from unicore_tpu.serve.engine import ServeEngine

    real = ServeEngine._dispatch

    def dropping(self, rows):
        real(self, rows)
        flat, tree = jax.tree_util.tree_flatten_with_path(self.pages)
        self.pages = jax.tree_util.tree_unflatten(tree, [
            leaf * 0 if "conv_tail" in jax.tree_util.keystr(path) else leaf
            for path, leaf in flat])

    monkeypatch.setattr(ServeEngine, "_dispatch", dropping)


LEFT_OUT = {"one expert of a token's left out": _three_experts,
            "the bias added to the combine weights": _bias_on_the_weights,
            "the tails dropped between steps": _tails_dropped}


@pytest.mark.parametrize("what", sorted(LEFT_OUT))
def test_a_run_that_leaves_part_of_the_mathematics_out_is_not_correct(
        what, root, tmp_path, monkeypatch):
    LEFT_OUT[what](monkeypatch)
    res = _run(root, tmp_path)
    assert res["correct"] is False
    assert res["failed"] == 0  # every request finished: only `correct` sees it


@pytest.mark.parametrize("seed", [1, 2, 3])
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


def test_the_experts_operations_and_bytes_by_hand():
    # a decode step of the cell: 32 rows x 4 experts, 55 of 64 touched, in
    # one layer at the published widths, float32 weights and rows
    per_expert = 3 * 2048 * 1536
    assert expert_counts.expert_params(2048, 1536) == per_expert == 9_437_184
    assert expert_counts.flops(128, 2048, 1536) == 128 * 2 * per_expert
    assert expert_counts.bytes_moved(128, 55, 2048, 1536, 4, 4) == (
        55 * per_expert * 4 + 128 * 2 * 2048 * 4)
    # nobody routed: nothing read, nothing multiplied
    assert expert_counts.flops(0, 2048, 1536) == 0
    assert expert_counts.bytes_moved(0, 0, 2048, 1536, 4, 4) == 0


PLANE = "/device:TPU:0"
STEPS = [(0.0, 0.5, 1), (0.5, 0.5, 16)]


def _ctx(root, ops, host=(), signatures=None, rows=(((1, 40),), ((16, 16),)),
         steps=STEPS):
    cell = spec.load_cell(CELL, root)
    return {"trace": Trace({PLANE: list(ops)}, list(host), signatures),
            "planes": [PLANE], "t0": 0, "t1": 1000, "window_s": 1e-6,
            "busy_s": 800e-9, "cell": cell, "rows": [list(r) for r in rows],
            "steps": list(steps), "pool_itemsize": 4,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


@pytest.fixture
def routed(monkeypatch):
    """The program's routing record, as two steps would leave it."""
    from unicore_tpu.ops import moe

    record = [(9, 9), (9, 9), (9, 9), (8, 6), (128, 32)]
    monkeypatch.setattr(moe, "routing_report", lambda: list(record))
    return record


def test_the_moe_readers_on_a_small_trace_by_hand(root, routed):
    ops = [("fusion.1", 0, 100), ("fusion.7", 100, 200),
           ("fusion.8", 300, 100), ("fusion.9", 400, 50),
           ("gather.2", 450, 30), ("while.1", 100, 300),
           ("ragged_paged_attention.1", 500, 300)]
    sig = {"fusion.1": "fusion.1 fusion f32[1,4,64]{2,1,0}",
           "fusion.7": "fusion.7 fusion f32[8,32]{1,0:T(8,128)S(1)}",
           "fusion.8": "fusion.8 fusion f32[12,8,64]{2,1,0}",
           "fusion.9": "fusion.9 fusion s32[16,8]{1,0}",
           "gather.2": "gather.2 gather f32[4,2,64]{2,1,0}",
           "while.1": "while.1 while (s32[], f32[12,8,64]{2,1,0}, "
                      "f32[8,64,32]{2,1,0})"}
    ctx = _ctx(root, ops, signatures=sig)
    # the window's two steps are the record's last two: (8, 6), (128, 32);
    # toy sizes (hidden 64, experts of 32): memory-bound at these peaks
    least = sum(expert_counts.bytes_moved(a, t, 64, 32, 4, 4) / 1e9
                for a, t in routed[-2:])
    # fusion.7 and fusion.8 are the matmuls (300 ns); the while that holds
    # them is not counted twice
    assert moe_readers.expert_roofline_pct(ctx) == pytest.approx(
        100.0 * least / 300e-9)
    # [rows, experts] and [rows, top_k, hidden]: 80 ns over two steps, ms
    assert moe_readers.overhead_ms_per_step(ctx) == pytest.approx(40e-6)
    # grouped queries: K/V bytes of 2 heads, queries of 4, one layer
    from benchmarks.kernels import ragged_paged_attention as rpa
    want = 0.0
    for rows in ctx["rows"]:
        b = (rpa.bytes_moved([(0, c) for _, c in rows], 2, 16, 4, 4)
             + rpa.bytes_moved([(q, 0) for q, _ in rows], 4, 16, 4, 4))
        want += max(rpa.flops(rows, 4, 16) / 1e12, b / 1e9)
    assert moe_readers.ragged_attn_roofline_pct(ctx) == pytest.approx(
        100.0 * want / 300e-9)


def test_the_patterns_take_their_sizes_from_the_configuration(root):
    tiny, real = spec.load_cell(CELL, root), spec.load_cell(LIKE)
    assert real["workload"]["kernel_events"] == {
        **tiny["workload"]["kernel_events"],
        "ragged_paged_attention": 'custom_call_target="tpu_custom_call"'}
    experts = moe_readers.event_pattern(real, "moe_experts")
    other = moe_readers.event_pattern(real, "moe_overhead")
    assert "1536" in experts.pattern and "2048" in experts.pattern
    # the loop's three fusions at both widths of the cell's step
    for result in ("f32[8,1536]{1,0:T(8,128)S(1)}", "f32[32,1536]{1,0}",
                   "f32[72,8,2048]{2,1,0}", "f32[126,32,2048]{2,1,0}"):
        assert experts.search(f"fusion.385 fusion {result}")
    # not the while that holds them, a dense layer's, the head's, the
    # attention's or the conv's results, nor the list itself
    for result in ("f32[1,32,2048]", "f32[1,512,2048]", "f32[32,11776]",
                   "f32[32,65536]", "f32[32,4,8,64]", "f32[32,3,6144]",
                   "f32[32,4,2048]", "f32[2048,2048]"):
        assert not experts.search(f"fusion.4 fusion {result}{{2,1,0}}")
    assert not experts.search("while.2 while (s32[], f32[72,8,2048]{2,1,0})")
    for result in ("f32[32,64]", "s32[128,64]", "s32[32,4]", "s32[64]",
                   "f32[512,4,2048]", "f32[72,8,2048]"):
        assert other.search(f"fusion.6 fusion {result}{{1,0}}")
    assert other.search("dynamic_slice.187 dynamic-slice s32[1]{0:T(128)}")
    assert not other.search("while.1 while (s32[], f32[72,8,2048]{2,1,0})")
    for result in ("f32[1,32,2048]", "f32[32,65536]", "f32[32,4,8,64]"):
        assert not other.search(f"fusion.6 fusion {result}{{2,1,0}}")
    assert moe_readers.event_pattern(real, "flash_attention") is None


@pytest.mark.parametrize("metric", _lfm2_metrics(), ids=lambda m: m["name"])
def test_each_reader_of_the_cell_through_the_loader(metric, root, routed):
    """Every per-layer metric of the cell has a reader file; one that
    reads PR 24's spans gives what its ``.docs`` or ``.chat`` sibling gives
    on the same trace; and none raises on a program that records nothing."""
    from test_bench_span_readers import HOST, NESTED, ctx_of

    base = os.path.join(spec.ROOT, "benchmarks")
    read = spec.load_reader(metric["name"], base)
    assert metric["moves"] == "serve_tokens_per_s"
    assert metric["workloads"] == [LIKE]
    stem = metric["name"].replace("-lfm2", "").replace(".lfm2", "")
    spans = ctx_of(HOST + NESTED)
    if metric["source"] == "program_span" and stem != "serve_state_ms":
        like = ".chat" if stem == "serve_decode_dispatch_ms" else ".docs"
        assert read(spans) == spec.load_reader(stem + like, base)(spans)
    if metric["source"] != "host_clock":
        bare = _ctx(root, [("fusion.1", 0, 100)], NESTED)
        if stem == "device_idle_pct":    # 800 of the 1000 ns were busy
            assert read(bare) == pytest.approx(20.0)
        else:
            assert read(bare) is None


BROKEN = {
    "no window bounds": lambda c: [c.pop("t0"), c.pop("t1")],
    "no trace at all": lambda c: c.pop("trace"),
    "no cell": lambda c: c.pop("cell"),
}


@pytest.mark.parametrize("how", sorted(BROKEN))
@pytest.mark.parametrize("reader", [
    moe_readers.expert_roofline_pct, moe_readers.overhead_ms_per_step,
    moe_readers.ragged_attn_roofline_pct], ids=lambda r: r.__name__)
def test_a_context_a_reader_cannot_read_is_nothing_to_read(
        reader, how, root, routed, capsys):
    ops = [("fusion.7", 100, 200), ("ragged_paged_attention.1", 500, 300)]
    ctx = _ctx(root, ops, signatures={
        "fusion.7": "fusion.7 fusion f32[8,32]{1,0}"})
    BROKEN[how](ctx)
    assert reader(ctx) is None
    assert "Error" in "".join(capsys.readouterr())


def test_a_program_without_the_record_gives_nothing_to_read(root,
                                                            monkeypatch):
    """The parent of PR 31 has no ``unicore_tpu.ops.moe``: the readers
    that need what the router did return None and do not raise."""
    import sys

    import unicore_tpu.ops

    monkeypatch.setitem(sys.modules, "unicore_tpu.ops.moe", None)
    monkeypatch.delattr(unicore_tpu.ops, "moe", raising=False)
    ops = [("fusion.7", 100, 200)]
    ctx = _ctx(root, ops, signatures={
        "fusion.7": "fusion.7 fusion f32[8,32]{1,0}"})
    assert moe_readers.expert_roofline_pct(ctx) is None
    assert moe_readers.overhead_ms_per_step(ctx) is None
