"""``chip_smoke.py`` away from the chip: it must refuse to run, and its
phase functions must rehearse at tiny widths on the CPU.

The phases run in subprocesses: they call ``cli_main`` in process, which
registers the example plugins, and the four-device rehearsal needs its
own ``--xla_force_host_platform_device_count``.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {repo!r})
    import chip_smoke as cs
    BERT = dict(cs.BERT, layers=1, dim=32, ffn=64, heads=2, seq=32,
                batch=8, symbols=60, updates=4, save_at=2, mesh_updates=2)
    LM = dict(cs.LM, layers=1, dim=32, ffn=64, heads=2, seq=64, batch=4,
              symbols=60, updates=2, page_size=4, num_pages=64,
              max_batch=4, prefill_chunk=8, max_new_tokens=4,
              prefix_len=8, prompt_lens=(3, 5, 9, 12, 17, 20, 6, 11))
    work = {work!r}
""")


def _run(code, devices, timeout=900):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _no_chip(script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_without_a_tpu():
    r = _no_chip(os.path.join(REPO, "chip_smoke.py"))
    assert r.returncode != 0
    assert "no tpu" in r.stderr
    assert '"ok"' not in r.stdout


def test_bench_refuses_without_a_tpu():
    r = _no_chip(os.path.join(REPO, "bench.py"))
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""  # no throughput, no JSON line


def test_train_and_serve_phases_rehearse_on_cpu(tmp_path):
    """The first rehearsal: both one-chip phases, imported and called
    directly (not through ``main``), one CPU device, tiny widths."""
    code = TINY.format(repo=REPO, work=str(tmp_path)) + textwrap.dedent("""
        out = {"fork": cs.fork_phase(),
               "barrier": cs.barrier_phase(n=128, reps=2),
               "train": cs.train_phase(work, 1, BERT),
               "serve": cs.serve_phase(work, 1, LM)}
        print(json.dumps(out, default=str))
    """)
    out = _run(code, devices=1)
    assert out["fork"]["same_stream_as_threads"]
    assert len(out["barrier"]["block_until_ready_ms"]) == 3
    train = out["train"]
    assert train["updates"] == 4 and len(train["losses"]) == 4
    assert any("_2.pt" in f for f in train["checkpoints"])
    assert train["platform"] == ["cpu"] and train["tpu_custom_call"] == 0
    assert train["memory_analysis_gb"]["estimated_peak_gb"] > 0
    # off the chip the dispatch is on record as the reference path
    assert set(train["kernel_dispatch"]["flash_attention"].values()) == {
        "reference"}
    serve = out["serve"]
    assert serve["requests"] == 10 and serve["prefix_hits"] >= 1
    assert serve["finish_reasons"] == ["length"]
    assert serve["host_faults"] == 0 and serve["quarantined"] == 0
    assert len(serve["attention_paths"]) == 2  # decode + prefill chunk
    for cmp in serve["vs_full_forward"].values():
        assert cmp["exact"] == cmp["tokens"] == 4


def test_kernel_phase_rehearses_on_cpu():
    """The kernel phase at a tiny width, interpreted: two of its row
    patterns, both latent forms and the sliding layer's rows (PR 43),
    either slot parity.  (Every pattern,
    and what the interpreters can say of the kernel's invariant:
    ``tests/test_serve.py`` ``test_ragged_kernel_row_patterns``.)"""
    import chip_smoke as cs

    tiny = dict(cs.KERNEL, page_size=8, heads=2, head_dim=64, rows=16,
                table_pages=24, widths=(3,), pages_per_block=(2,),
                patterns=("zeros_between", "odd_even"), latent_heads=2,
                latent_rows=11, latent_chunk=16)
    rep = cs.kernel_phase(1, tiny)
    assert rep["cases"] == 10 and rep["gap_max"] < 2e-5
    # a pool too small for the rows' pages is refused, not wrapped around
    with pytest.raises(cs.SmokeFailure, match="do not fit"):
        cs.ragged_row_case("odd_even", dict(tiny, num_pages=8), 2, 0, 3, 1)


def test_mesh_phase_rehearses_on_four_virtual_devices(tmp_path):
    """The second rehearsal: what ``--chips 4`` runs, on four virtual
    CPU devices."""
    code = TINY.format(repo=REPO, work=str(tmp_path)) + textwrap.dedent("""
        import jax
        print(json.dumps(cs.mesh_phase(work, 1, BERT, jax.devices()),
                         default=str))
    """)
    out = _run(code, devices=4)
    assert sorted(out) == ["data4", "fsdp2", "one", "tp2"]
    assert out["one"]["mesh"]["data"] == 1
    assert out["data4"]["mesh"]["data"] == 4
    assert out["data4"]["all_reduce"] > 0 and out["one"]["all_reduce"] == 0
    assert out["fsdp2"]["opt_state_sharded"]
    assert out["tp2"]["attention_kernel_sharded"]
    for rep in out.values():
        assert rep["loss_rel_err_vs_one"] <= 2e-2


def test_compile_cache_follows_the_variable_or_stays_put(monkeypatch):
    import jax

    from unicore_tpu.utils import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    was = jax.config.jax_compilation_cache_dir
    try:
        assert configure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == was  # nothing set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first, second = configure_compile_cache(), configure_compile_cache()
        assert first == second == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert str(os.getpid()) not in first and "tmp" not in first
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
