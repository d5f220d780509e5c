"""Whole serve-cell runs at a toy width on the CPU (open loop and closed
loop), the same run with a token altered where it is produced, and the
bfloat16 control."""

import os
import time
import types

import jax
import numpy as np
import pytest

import bench_tiny
from benchmarks import run as bench_run
from benchmarks.lib import check, serve_cell, spec, weights


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, tmp_path, name, seed=3_000_000_017, seconds=2.0):
    cell = spec.load_cell(name, root)
    return bench_run.run_cell(
        cell, seed, seconds, False, jax.devices()[:1], peaks=None,
        workdir=os.path.join(str(tmp_path), "work"),
        process_t0=time.perf_counter())


@pytest.mark.parametrize("name,metrics", [
    ("tiny_chat", {"serve_ttft_p90_ms", "serve_itl_p95_ms", "setup_s"}),
    ("tiny_docs", {"serve_tokens_per_s", "setup_s"}),
])
def test_a_sound_run_is_correct_and_reports_the_cells_metrics(
        root, tmp_path, name, metrics):
    res = _run(root, tmp_path, name)
    assert res["correct"] is True
    assert res["attempted"] > 5 and res["failed"] == 0
    assert set(res["metrics"]) == metrics
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, tmp_path, monkeypatch):
    from unicore_tpu.serve.engine import ServeEngine

    real = ServeEngine._pick_tokens

    def altered(logits, *args):
        return (real(logits, *args) + 1) % logits.shape[-1]

    monkeypatch.setattr(ServeEngine, "_pick_tokens", staticmethod(altered))
    res = _run(root, tmp_path, "tiny_chat")
    assert res["correct"] is False
    assert res["failed"] == 0  # every request finished: only `correct` sees it


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bf16_control_fails_the_cells_limits(root, seed):
    """The control is the reference in bfloat16 put in the program's
    place: what it serves is what bfloat16 puts first.  At the toy width
    bfloat16 moves an argmax only once in some hundreds of positions, so
    it is read over more positions than a toy window serves (the tokens
    are only context here: what the control picks does not depend on
    what was served)."""
    cell = spec.load_cell("tiny_chat", root)
    cfg, limits = cell["config"], cell["workload"]["limits"]
    model = cell["family"].build_model(cfg)
    params = weights.make(serve_cell.abstract_params(model), seed,
                          scales=cfg.get("weight_scales"))
    rng = np.random.default_rng(seed)
    sample = [types.SimpleNamespace(
        spec={"prompt": rng.integers(4, cfg["vocab_size"], 2).tolist()},
        seq=types.SimpleNamespace(
            generated=rng.integers(4, cfg["vocab_size"], 250).tolist()))
        for _ in range(40)]
    _, lower = check.serve_gaps(params, cell, sample)
    assert len(lower) == 40 * 250
    control = check.serve_numbers(lower, lower)
    assert control["moved"] > 0
    assert control["gap_share_of_bf16"] == 1.0 > limits["gap_share_of_bf16"]
    # an exact program on the same tokens reads 0 on both numbers
    exact = check.serve_numbers([0.0] * len(lower), lower)
    assert exact["gap_share_of_bf16"] == 0.0 and exact["logit_gap_max"] == 0.0


@pytest.mark.parametrize("served,lower,share", [
    ([0.0, 0.0], [0.0, 0.0], 0.0),          # nothing moved either way
    ([0.0, 0.1], [0.0, 0.0], float("inf")),  # moved where bf16 moves none
    ([0.0, 0.1], [0.2, 0.2], 0.25),
])
def test_the_share_of_bf16s_gaps(served, lower, share):
    assert check.serve_numbers(served, lower)["gap_share_of_bf16"] == share
