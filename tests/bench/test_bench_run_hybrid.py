"""The hybrid family through the harness at a toy width on the CPU: a
whole closed-loop run, the same run with the recurrent state dropped
between steps, the bfloat16 control, and the new readers and counts on
inputs whose answers can be worked out by hand."""

import os
import time
import types

import jax
import numpy as np
import pytest

import bench_tiny_hybrid
from benchmarks import run as bench_run
from benchmarks.kernels import gated_delta_rule as gdn_counts
from benchmarks.lib import check, hybrid_readers, serve_cell, spec, weights
from benchmarks.lib.trace import Trace

CELL = bench_tiny_hybrid.CELL


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny_hybrid.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, tmp_path, seed=3_000_000_019, seconds=3.0):
    cell = spec.load_cell(CELL, root)
    return bench_run.run_cell(
        cell, seed, seconds, False, jax.devices()[:1], peaks=None,
        workdir=os.path.join(str(tmp_path), "work"),
        process_t0=time.perf_counter())


def test_the_hybrid_cell_loads_with_its_family_and_its_metrics(root):
    cell = spec.load_cell(CELL, root)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"gdn_roofline_pct.hybrid", "linear_attn_device_pct.hybrid",
            "serve_state_ms.hybrid", "ragged_attn_roofline_pct.hybrid",
            "device_idle_pct.hybrid"} <= names
    dims = cell["family"].dims(cell["config"])
    # `layers` is what the ragged reader multiplies by: the layers that
    # hold pages, not the depth
    assert dims["layers"] == 2 and dims["linear_layers"] == 6
    assert (dims["heads"], dims["head_dim"]) == (2, 32)
    assert (dims["linear_heads"], dims["linear_key_dim"],
            dims["linear_value_dim"]) == (2, 24, 48)


def test_a_sound_run_is_correct_and_reports_the_cells_metrics(
        root, tmp_path):
    res = _run(root, tmp_path)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_a_run_that_drops_the_state_between_steps_is_not_correct(
        root, tmp_path, monkeypatch):
    """The pages are kept and the recurrent state is not: every token is
    computed from a state that forgot the document."""
    from unicore_tpu.serve.engine import ServeEngine

    real = ServeEngine._dispatch

    def dropping(self, rows):
        real(self, rows)
        flat, tree = jax.tree_util.tree_flatten_with_path(self.pages)
        self.pages = jax.tree_util.tree_unflatten(tree, [
            leaf * 0 if "ssm_state" in jax.tree_util.keystr(path) else leaf
            for path, leaf in flat])

    monkeypatch.setattr(ServeEngine, "_dispatch", dropping)
    res = _run(root, tmp_path)
    assert res["correct"] is False
    assert res["failed"] == 0  # every request finished: only `correct` sees it


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bf16_control_fails_the_cells_limits(root, seed):
    """As for ``decoder_lm``: the reference with weights, activations,
    cache AND recurrent state in bfloat16, put in the program's place."""
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


def test_the_rules_operations_and_bytes_on_one_row_by_hand():
    # one prefill row of 128 tokens, 30 heads of 96 x 192 (the published
    # sizes): per token and head 7 * 96 * 192 operations; the state
    # 30 * 96 * 192 floats read and written, q, k (96) and v, o (192) once
    rows = [(128, 2048)]
    assert gdn_counts.flops(rows, 30, 96, 192) == 7 * 128 * 30 * 96 * 192
    assert gdn_counts.bytes_moved(rows, 30, 96, 192, 4, 4) == (
        2 * 30 * 96 * 192 * 4 + 128 * 30 * 2 * (96 + 192) * 4)
    # a decode row moves the same state for one token
    assert gdn_counts.bytes_moved([(1, 3000)], 30, 96, 192, 4, 4) == (
        2 * 30 * 96 * 192 * 4 + 30 * 2 * (96 + 192) * 4)
    assert gdn_counts.flops([], 30, 96, 192) == 0


PLANE = "/device:TPU:0"


def _ctx(root, ops, host, signatures=None, rows=((128, 2048),)):
    cell = spec.load_cell(CELL, root)
    return {"trace": Trace({PLANE: ops}, host, signatures),
            "planes": [PLANE], "t0": 0, "t1": 1000, "window_s": 1e-6,
            "busy_s": 800e-9, "cell": cell, "rows": [list(rows)],
            "pool_itemsize": 4,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


def test_the_hybrid_readers_on_a_small_trace_by_hand(root):
    ops = [("fusion.1", 0, 100), ("fusion.7", 100, 300),
           ("fusion.9", 400, 100), ("ragged_paged_attention.1", 500, 300)]
    # what an event is known by: name, opcode, result type (no operands)
    sig = {"fusion.7": "fusion.7 fusion (f32[4,2,24,48]{3,2,1,0}, "
                       "f32[4,4,2,16,48]{4,3,2,1,0})",
           "fusion.9": "fusion.9 fusion f32[4,19,192]{2,1,0}",
           "fusion.1": "fusion.1 fusion f32[4,16,64]{2,1,0}"}
    host = [("serve/step", 0, 500), ("serve/state", 10, 20),
            ("serve/state", 100, 30), ("serve/step", 500, 500)]
    ctx = _ctx(root, ops, host, sig)
    # (300 + 100) ns of the 800 ns the device was busy
    assert hybrid_readers.linear_attn_device_pct(ctx) == pytest.approx(50.0)
    # 50 ns of serve/state over two steps, in ms
    assert hybrid_readers.state_ms_per_step(ctx) == pytest.approx(25e-6)
    # toy sizes (2 heads of 24 x 48, 6 linear layers), memory-bound at
    # these peaks: bytes over 1e9 B/s, over 300 ns of events
    b = gdn_counts.bytes_moved([(128, 2048)], 2, 24, 48, 4, 4)
    assert hybrid_readers.gdn_roofline_pct(ctx) == pytest.approx(
        100.0 * 6 * (b / 1e9) / 300e-9)


def test_the_patterns_take_their_sizes_from_the_configuration(root):
    """No batch, head count or width is spelled in a workload file: a
    cell of another size is matched by the same templates."""
    tiny = spec.load_cell(CELL, root)
    assert hybrid_readers.event_pattern(
        tiny, "gated_delta_rule").pattern == r"f32\[(\d+,)?4,2,"
    assert hybrid_readers.event_pattern(
        tiny, "short_conv").pattern == r"[\[,]192\]"
    real = spec.load_cell(bench_tiny_hybrid.LIKE)
    assert real["workload"]["kernel_events"] == {
        **tiny["workload"]["kernel_events"],
        "ragged_paged_attention": 'custom_call_target="tpu_custom_call"'}
    rule = hybrid_readers.event_pattern(real, "gated_delta_rule")
    conv = hybrid_readers.event_pattern(real, "short_conv")
    B = real["config"]["engine"]["max_batch"]
    assert rule.pattern == rf"f32\[(\d+,)?{B},30," and "11520" in conv.pattern
    # the state, and the rule's chunked operands with the scan's axis first
    assert rule.search(f"fusion.3 fusion f32[{B},30,96,192]{{3,2,1,0}}")
    assert rule.search(f"copy.9 copy f32[2,{B},30,32,192]{{4,3,2,1,0}}")
    # not a full-attention layer's, the FFN's or the head's results
    for other in (f"f32[{B},64,30,128]", f"f32[{B},1,3840]",
                  f"f32[{B},64,11008]", f"f32[{B},64,100352]",
                  f"bf16[{B},30,96,192]"):
        assert not rule.search(f"fusion.4 fusion {other}{{3,2,1,0}}")
        assert not conv.search(f"fusion.4 fusion {other}{{3,2,1,0}}")
    assert conv.search(f"fusion.5 fusion f32[{B},67,11520]{{2,1,0}}")
    assert hybrid_readers.event_pattern(real, "flash_attention") is None


def test_rows_served_with_no_event_matched_is_said_loudly(root, capsys):
    ops = [("fusion.1", 0, 100), ("ragged_paged_attention.1", 500, 300)]
    ctx = _ctx(root, ops, [("serve/step", 0, 500)])
    assert hybrid_readers.gdn_roofline_pct(ctx) is None
    assert "NO device event matches" in "".join(capsys.readouterr())


def _hybrid_metrics():
    return [m for m in spec.load_benchmark()["per_layer"]
            if bench_tiny_hybrid.LIKE in m.get("workloads", ())]


@pytest.mark.parametrize("metric", _hybrid_metrics(), ids=lambda m: m["name"])
def test_each_reader_of_the_hybrid_cell_through_the_loader(metric, root):
    """Every per-layer metric of the cell has a reader file; one that
    reads PR 24's spans gives what its ``.docs`` sibling gives on the same
    trace; and none raises on a program that records nothing."""
    from test_bench_span_readers import HOST, NESTED, ctx_of

    base = os.path.join(spec.ROOT, "benchmarks")
    read = spec.load_reader(metric["name"], base)
    assert metric["moves"] == "serve_tokens_per_s"
    stem = metric["name"].replace("-hybrid", "").replace(".hybrid", "")
    spans = ctx_of(HOST + NESTED)
    if metric["source"] == "program_span" and stem != "serve_state_ms":
        assert read(spans) == spec.load_reader(stem + ".docs", base)(spans)
    if metric["source"] != "host_clock":
        bare = _ctx(root, [("fusion.1", 0, 100)], NESTED)
        if stem == "device_idle_pct":    # 800 of the 1000 ns were busy
            assert read(bare) == pytest.approx(20.0)
        else:
            assert read(bare) is None


def test_an_event_is_known_by_its_name_opcode_and_result_type():
    text = ("%multiply_add_fusion.3 = (f32[16,30,96,192]{3,2,1,0:T(8,128)}, "
            "f32[16,30,96,192]{3,2,1,0}) fusion(f32[16,30,96,64]{3,2,1,0} "
            "%custom-call.61, f32[16,128,3840]{2,1,0} %p), kind=kLoop")
    known = hybrid_readers._known_by(text)
    assert known.startswith("multiply_add_fusion.3 fusion (f32[16,30,96,192]")
    assert "custom-call.61" not in known and "3840" not in known
    assert hybrid_readers._known_by("fusion.5") == "fusion.5"


def test_a_program_without_the_shapes_or_the_span_gives_nothing_to_read(
        root):
    ops = [("fusion.1", 0, 100), ("ragged_paged_attention.1", 500, 300)]
    ctx = _ctx(root, ops, [("serve/step", 0, 500)])
    assert hybrid_readers.gdn_roofline_pct(ctx) is None
    assert hybrid_readers.linear_attn_device_pct(ctx) is None
    assert hybrid_readers.state_ms_per_step(ctx) is None
    # and a context a reader cannot make sense of is nothing to read too
    assert hybrid_readers.gdn_roofline_pct({}) is None
