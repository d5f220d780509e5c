"""The ``laguna_lm`` family through the harness at a toy width on the CPU:
the cell ADDED to the tests' benchmark root as new files and entries, a
whole closed-loop run of short and long prompts in one queue over two kinds
of page, the same run with pieces of the model's mathematics left out in
turn (the chip's controls, PERF.md section 6, leave out more), and the new
readers and counts on inputs whose answers can be worked out by hand."""

import json
import os
import shutil
import time

import jax
import numpy as np
import pytest

import bench_tiny
from benchmarks import run as bench_run
from benchmarks.kernels import windowed_attention as counts
from benchmarks.lib import serve_cell, spec, window_readers
from benchmarks.lib.trace import Trace

CELL, CONFIG, MIX, LIKE = ("tiny_laguna_mixedlen", "tiny_laguna",
                           "tiny_laguna_mixedlen", "laguna_swa_mixedlen")


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


def test_the_cell_loads_with_its_family_and_its_seventeen_metrics(root):
    cell = spec.load_cell(CELL, root)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    mine = [m for m in spec.load_benchmark()["per_layer"]
            if LIKE in m.get("workloads", ())]
    assert {m["name"] for m in cell["per_layer"]} == {m["name"] for m in mine}
    assert len(cell["per_layer"]) == 17
    assert all(m["workloads"] == [LIKE] for m in mine)
    for m in mine:  # every reader is there to be loaded
        spec.load_reader(m["name"], cell["base"])
    real = spec.load_cell(LIKE)
    assert real["family"].dims(real["config"]) == {
        "layers": 9, "global_layers": 3, "sliding_layers": 6, "heads": 48,
        "sliding_heads": 64, "kv_heads": 8, "head_dim": 128, "window": 512,
        "hidden": 2048, "expert_layers": 8, "experts": 256,
        "experts_held": 64, "experts_per_token": 8, "expert_width": 512}
    dims = cell["family"].dims(cell["config"])
    assert (dims["global_layers"], dims["sliding_layers"],
            dims["expert_layers"]) == (2, 3, 4)
    assert (dims["experts"], dims["experts_held"]) == (16, 8)


def test_the_configuration_holds_every_published_number():
    """The catalog's ``config`` under the same keys; what differs is
    listed in ``reduced`` (``BENCHMARK.json`` and the file agree), and no
    width is among it."""
    kinds = ["full_attention"] + ["sliding_attention"] * 3
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5,
        "layer_types": kinds * 10,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096}}
    cell = spec.load_cell(LIKE)
    cfg, entry = cell["config"], cell["config_entry"]
    differs = {k for k, v in published.items() if cfg[k] != v}
    assert differs == {"num_hidden_layers", "layer_types", "mlp_layer_types",
                       "num_attention_heads_per_layer", "num_experts",
                       "vocab_size", "max_position_embeddings"}
    assert differs | {"torch_dtype"} == set(entry["reduced"])
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in entry["reduced"])
    # the cut: published layers 0-8, their first nine entries
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert cfg[key] == published[key][:9]
    # the floors: two whole periods behind the dense layer, 64 >= 8
    # experts held, a quarter >= an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 9
    assert (cfg["num_experts"], cfg["router_outputs"]) == (64, 256)
    assert cfg["vocab_size"] * 4 == published["vocab_size"]
    assert "one four-chip host shares each layer" in cfg["deployment"]
    assert "2,108,758,016 parameters = 8.44 GB" in cfg["deployment"]
    for key in ("block", "gate", "window_mask", "qk_norm", "router",
                "swiglu", "pad_token_id", "weights", "caches", "engine"):
        assert cfg["assumed"][key]
    assert "98,304" in cfg["assumed"]["gate"]  # the gate's parameters
    traffic = cell["traffic"]
    assert traffic == {
        "kind": "closed_loop", "clients": 32,
        "document": {"dist": "lognormal", "median": 2048, "sigma": 1.0,
                     "min": 256, "max": 16384},
        "asks_per_document": 1,
        "question": {"dist": "uniform", "min": 16, "max": 64},
        "answer": {"dist": "uniform", "min": 128, "max": 256},
        "ramp_s": 15}
    # every compared sequence fits the positions, and check.py's padding
    # to quarters of them keeps to four lengths
    assert 16384 + 64 + 256 <= cfg["max_position_embeddings"] == 4 * 4224


def test_the_weights_are_the_reckoned_8_44_gb():
    cell = spec.load_cell(LIKE)
    abstract = serve_cell.abstract_params(
        cell["family"].build_model(cell["config"]))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(abstract))
    assert n == 2_108_758_016 and round(n * 4 / 1e9, 2) == 8.44


def test_a_sound_run_is_correct_and_reports_the_cells_metrics(
        root, tmp_path):
    res = _run(root, tmp_path)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def _whole_context_in_sliding_layers(monkeypatch):
    from unicore_tpu.serve import attention

    real = attention.paged_attention_reference

    def unmasked(*args, window=0, **kwargs):
        return real(*args, **kwargs)

    monkeypatch.setattr(attention, "paged_attention_reference", unmasked)


def _a_page_released_early(monkeypatch):
    from unicore_tpu.serve import kv_pool

    real = kv_pool.PagedKVPool.window_release

    def early(self, seq_id, next_position):
        return real(self, seq_id, next_position + self.page_size)

    monkeypatch.setattr(kv_pool.PagedKVPool, "window_release", early)


def _gate_left_out(monkeypatch):
    from unicore_tpu.modules import pattern_decoder

    real = pattern_decoder.Linear.__call__

    def without(self, x):
        out = real(self, x)
        # sigmoid(40) is 1: every head passes whole
        return out * 0 + 40.0 if self.name == "g_proj" else out

    monkeypatch.setattr(pattern_decoder.Linear, "__call__", without)


def _plain_rotary_in_global_layers(monkeypatch):
    from unicore_tpu.modules import rotary

    monkeypatch.setattr(
        rotary, "yarn_inv_freq",
        lambda dim, theta, *rest: theta ** (
            -np.arange(dim // 2, dtype=np.float64) * 2.0 / dim))


LEFT_OUT = {
    "a sliding layer attending its whole context":
        _whole_context_in_sliding_layers,
    "a window page released one page early": _a_page_released_early,
    "the gate left out": _gate_left_out,
    "YaRN's factor left out": _plain_rotary_in_global_layers,
}


@pytest.mark.parametrize("what", sorted(LEFT_OUT))
def test_a_run_that_leaves_part_of_the_mathematics_out_is_not_correct(
        what, root, tmp_path, monkeypatch):
    LEFT_OUT[what](monkeypatch)
    res = _run(root, tmp_path, seconds=1.5)
    assert res["correct"] is False
    assert res["failed"] == 0  # every request finished: only `correct` sees it


def test_the_windowed_attentions_operations_and_bytes_by_hand():
    # the published widths: 64 query heads over 8 K/V heads of 128, a
    # window of 512, float32 pages and activations
    # a decode row over 6,000 keys sees 512 of them in a sliding layer
    assert counts.flops([(1, 6000)], 64, 128, 512) == 4 * 512 * 64 * 128
    assert counts.flops([(1, 6000)], 48, 128) == 4 * 6000 * 48 * 128
    assert counts.bytes_moved([(1, 6000)], 64, 8, 128, 4, 4, 512) == (
        2 * 512 * 8 * 128 * 4 + 2 * 64 * 128 * 4)
    assert counts.bytes_moved([(1, 6000)], 48, 8, 128, 4, 4) == (
        2 * 6000 * 8 * 128 * 4 + 2 * 48 * 128 * 4)
    # a row shorter than the window: what it has
    assert counts.flops([(1, 100)], 64, 128, 512) == 4 * 100 * 64 * 128
    # a chunk of 128 ending at 4,224: every query has its 512 keys, and
    # the row needs the 512 + 127 keys some query sees, once
    assert counts.flops([(128, 4224)], 64, 128, 512) == (
        4 * 128 * 512 * 64 * 128)
    assert counts.visible_keys(128, 4224, 512) == 639
    # a chunk that starts the sequence: token i has i + 1 keys
    assert counts.flops([(128, 128)], 64, 128, 512) == (
        4 * sum(range(1, 129)) * 64 * 128)
    assert counts.flops([(128, 128)], 64, 128, 512) == counts.flops(
        [(128, 128)], 64, 128)
    assert counts.flops([], 64, 128, 512) == 0


PLANE = "/device:TPU:0"


def _ctx(root, ops, host=(), signatures=None):
    cell = spec.load_cell(CELL, root)
    return {"trace": Trace({PLANE: list(ops)}, list(host), signatures),
            "planes": [PLANE], "t0": 0, "t1": 1000, "window_s": 1e-6,
            "busy_s": 800e-9, "cell": cell,
            "rows": [[(1, 40)], [(16, 48), (1, 33)]],
            "steps": [(0.0, 0.5, 1), (0.5, 0.5, 16)], "pool_itemsize": 4,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


def test_the_window_readers_on_a_small_trace_by_hand(root):
    # toy sizes: 6 / 8 query heads over 2 K/V heads of 16, so 3 / 4 query
    # cells a token; widths 1 and 16: a sliding call's result has 4 or 64
    # cells, a global one's 3 or 48, over 32 lanes
    ops = [("rpa.1", 0, 100), ("rpa.2", 100, 200), ("fusion.3", 300, 50),
           ("rpa.4", 400, 100), ("rpa.5", 500, 60)]
    sig = {"rpa.1": 'rpa.1 custom-call f32[4,3,32]{2,1,0} '
                    'custom_call_target="tpu_custom_call"',
           "rpa.2": 'rpa.2 custom-call f32[4,4,32]{2,1,0} '
                    'custom_call_target="tpu_custom_call"',
           "fusion.3": "fusion.3 fusion f32[4,4,32]{2,1,0}",
           "rpa.4": 'rpa.4 custom-call f32[4,64,32]{2,1,0} '
                    'custom_call_target="tpu_custom_call"',
           "rpa.5": 'rpa.5 custom-call f32[4,48,32]{2,1,0} '
                    'custom_call_target="tpu_custom_call"'}
    ctx = _ctx(root, ops, signatures=sig)
    sliding = window_readers.event_pattern(ctx["cell"], "ragged_sliding")
    assert [n for n in sig if sliding.search(sig[n])] == ["rpa.2", "rpa.4"]
    assert window_readers.window_attn_device_pct(ctx) == pytest.approx(
        100.0 * 300 / 800)
    least = 0.0
    for rows in ctx["rows"]:
        for layers, heads, window in ((2, 6, 0), (3, 8, 16)):
            f = counts.flops(rows, heads, 16, window)
            b = counts.bytes_moved(rows, heads, 2, 16, 4, 4, window)
            least += max(f / 1e12, b / 1e9) * layers
    assert window_readers.ragged_attn_roofline_pct(ctx) == pytest.approx(
        100.0 * least / 460e-9)
    # nothing of the kind in the trace: nothing to read
    bare = _ctx(root, [("fusion.3", 300, 50)], signatures=sig)
    assert window_readers.ragged_attn_roofline_pct(bare) is None
    assert window_readers.window_attn_device_pct(bare) is None


def test_the_release_span_and_the_residency_by_hand(root, monkeypatch):
    host = [("serve/step", 0, 400), ("serve/window-release", 10, 30),
            ("serve/step", 500, 400), ("serve/window-release", 520, 50)]
    ctx = _ctx(root, [("rpa.1", 0, 100)], host=host)
    assert window_readers.window_release_ms_per_step(ctx) == pytest.approx(
        80 / 1e6 / 2)
    assert window_readers.window_release_ms_per_step(
        _ctx(root, [("rpa.1", 0, 100)], host=host[:1])) is None
    from unicore_tpu.serve import step_log

    log = step_log.StepLog()
    t = time.perf_counter()
    log.write(1, 1, 3, 4, 3, False, 0.1, 40, 100)
    log.write(2, 16, 30, 64, 1, False, 0.2, 30, 50)
    log.write(3, 1, 3, 4, 3, True, 0.1, 0, 0)   # an idle pool: left out
    monkeypatch.setattr(step_log, "_latest", (log, step_log.FirstTokenLog()))
    ctx["steps"] = [(t, time.perf_counter() - t + 1.0, 1)]
    assert window_readers.kv_resident_vs_one_table_pct(ctx) == pytest.approx(
        100.0 * (0.4 + 0.6) / 2)
