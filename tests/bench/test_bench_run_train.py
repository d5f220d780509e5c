"""A whole train-cell run at a toy width on the CPU: everything after the
harness's look for a chip.  The plain reference follows the program; the
same run with the timed path broken underneath comes out not correct; and
the control (the reference with float8 matmul operands in the program's
place) fails a limit that the program keeps."""

import os
import time

import jax
import pytest

import bench_tiny
from benchmarks import control, run as bench_run
from benchmarks.lib import spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, tmp_path, seed=3_000_000_017, seconds=1.0):
    cell = spec.load_cell("tiny_mlm", root)
    return bench_run.run_cell(
        cell, seed, seconds, False, jax.devices()[:1], peaks=None,
        workdir=os.path.join(str(tmp_path), "work"),
        process_t0=time.perf_counter())


def test_a_sound_run_is_correct_and_reports_the_cells_metrics(root, tmp_path):
    res = _run(root, tmp_path)
    assert res["correct"] is True
    assert res["attempted"] > 5 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert res["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert not os.path.exists(os.path.join(str(tmp_path), "work"))


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, tmp_path, monkeypatch):
    import jax.numpy as jnp

    from unicore_tpu.trainer import Trainer

    real = Trainer._dispatch_train_step

    def broken(self, state, *args):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        _, stats = real(self, state, *args)
        return kept, stats  # the update's work is thrown away

    monkeypatch.setattr(Trainer, "_dispatch_train_step", broken)
    res = _run(root, tmp_path)
    assert res["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_fails_a_limit_the_program_keeps(root, tmp_path, seed):
    cell = spec.load_cell("tiny_mlm", root)
    limits = cell["workload"]["limits"]
    got = control.readings(cell, seed, 0.5, jax.devices()[:1],
                           os.path.join(str(tmp_path), "work"), [], "program")
    assert all(got["program"][k] <= limits[k] for k in limits), got
    assert any(got["control"][k] > limits[k] for k in limits), got
