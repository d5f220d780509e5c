"""The command: no TPU is a failure, never a CPU fall-back; nor does it
run from a directory that holds the benchmark without the program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "bert_base_mlm", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *ARGS],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result_line(out):
    for line in out.stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except ValueError:
            continue
    return True


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    out = _run(REPO)
    assert out.returncode != 0
    assert "not a tpu" in out.stderr
    assert _no_result_line(out)


def test_without_the_program_the_command_fails_and_prints_no_result(tmp_path):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(REPO, p), os.path.join(tmp_path, p),
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert _no_result_line(out)


@pytest.mark.parametrize("script", ["control.py", "sweep.py"])
def test_the_builders_tools_refuse_the_cpu_too(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    extra = (["--seeds", "1"] if script == "control.py"
             else ["--rates", "1"])
    out = subprocess.run(
        [sys.executable, os.path.join("benchmarks", script),
         "--workload", "opt13b_chat", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and "not a tpu" in out.stderr
