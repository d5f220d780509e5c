"""Kernel-autotuning subsystem (ops/tuning): cache round-trip +
environment-fingerprint invalidation, shape bucketing boundaries,
eager-crossover dispatch, tuned-config threading (probe keys, row
blocks, flash blocks), and deterministic tuner picks under interpret
mode with fixed fake timings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu import ops
from unicore_tpu.ops import tuning
from unicore_tpu.ops.tuning import TuneCache, bucket_key, candidates
from unicore_tpu.ops.tuning.tuner import tune_bucket, tune_workloads


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    """Isolated cache file + clean tuning state; restores state after."""
    path = str(tmp_path / "tune_cache.json")
    cache = TuneCache(paths=[path], fingerprint="fmtT|testdev|jaxT|libtpuT")
    tuning.reset(mode="cache")
    monkeypatch.setattr(tuning, "get_cache", lambda: cache)
    yield cache
    tuning.reset(mode="cache")


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "c.json")
    c1 = TuneCache(paths=[path], fingerprint="fp1")
    c1.record("softmax_dropout|k1", {"q_blk": 64}, micros_us={"eager": 10.0})
    c1.record("softmax_dropout|k2", "eager")
    c2 = TuneCache(paths=[path], fingerprint="fp1")
    assert c2.lookup("softmax_dropout|k1") == {"q_blk": 64}
    assert c2.lookup("softmax_dropout|k2") == "eager"
    assert c2.get("softmax_dropout|k1")["micros_us"] == {"eager": 10.0}
    assert c2.lookup("softmax_dropout|missing") is None


def test_cache_version_key_invalidation(tmp_path):
    """An entry tuned under another environment fingerprint (device
    kind / jax / libtpu change) must read as a miss — stale configs
    self-invalidate to the heuristic path."""
    path = str(tmp_path / "c.json")
    TuneCache(paths=[path], fingerprint="v5e|jax0.4").record(
        "flash|k", {"block_q": 512, "block_k": 2048}
    )
    stale = TuneCache(paths=[path], fingerprint="v4|jax0.5")
    assert stale.lookup("flash|k") is None
    # and the original fingerprint still sees it
    assert TuneCache(paths=[path], fingerprint="v5e|jax0.4").lookup(
        "flash|k"
    ) == {"block_q": 512, "block_k": 2048}


def test_cache_dry_entries_never_steer_dispatch(tmp_path):
    """Fake-timing (dry-run) entries are reused by the tuner's
    warm-cache check but must read as misses for dispatch decisions."""
    path = str(tmp_path / "c.json")
    c = TuneCache(paths=[path], fingerprint="fp")
    c.record("k", {"q_blk": 8}, source="dry")
    assert c.lookup("k") is None
    assert c.get("k")["winner"] == {"q_blk": 8}
    c.record("k", {"q_blk": 8}, source="timed")
    assert c.lookup("k") == {"q_blk": 8}


def test_cache_overlay_wins_and_corrupt_reads_empty(tmp_path):
    repo = tmp_path / "repo.json"
    overlay = tmp_path / "overlay.json"
    TuneCache(paths=[str(repo)], fingerprint="fp").record("k", "eager")
    c = TuneCache(paths=[str(repo), str(overlay)], fingerprint="fp")
    assert c.lookup("k") == "eager"
    c.record("k", {"q_blk": 8})
    c2 = TuneCache(paths=[str(repo), str(overlay)], fingerprint="fp")
    assert c2.lookup("k") == {"q_blk": 8}
    # the overlay write must not have clobbered the repo layer
    assert TuneCache(paths=[str(repo)], fingerprint="fp").lookup("k") == "eager"
    # corrupt file -> empty cache, no raise
    overlay.write_text("{not json")
    c3 = TuneCache(paths=[str(overlay)], fingerprint="fp")
    assert c3.lookup("k") is None


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------


def test_pow2_bucket_boundaries():
    assert tuning.pow2_bucket(1) == 1
    assert tuning.pow2_bucket(128) == 128
    assert tuning.pow2_bucket(129) == 256
    assert tuning.pow2_bucket(384) == 512
    assert tuning.pow2_bucket(512) == 512
    assert tuning.pow2_bucket(513) == 1024


def test_sd_bucket_rounds_rows_keeps_patterns():
    wl_a = tuning.sd_workload((32, 12, 512, 512), "bfloat16",
                              bias=((1, 12, 512, 512), "bfloat16"))
    wl_b = tuning.sd_workload((8, 4, 400, 512), "bfloat16",
                              bias=((1, 4, 400, 512), "bfloat16"))
    # lead dims and exact row counts wash out (400 -> 512)
    assert candidates.OPS["softmax_dropout"].bucket(wl_a) == \
        candidates.OPS["softmax_dropout"].bucket(wl_b)
    # a different broadcast pattern is a different bucket
    wl_c = tuning.sd_workload((32, 12, 512, 512), "bfloat16",
                              bias=((1, 1, 512, 512), "bfloat16"))
    assert candidates.OPS["softmax_dropout"].bucket(wl_a) != \
        candidates.OPS["softmax_dropout"].bucket(wl_c)


def test_flash_bucket_exact_head_dim_and_bias_class():
    mk = lambda d, bias: tuning.flash_workload(
        (4, 512, 8, d), 512, "bfloat16", bias=bias, dropout_on=True,
    )
    b = candidates.OPS["flash_attention"].bucket
    # head-dim is exact: 64 vs 80 are different buckets
    assert b(mk(64, None)) != b(mk(80, None))
    # bias-head broadcastness does NOT split the bucket (see
    # candidates._flash_bias_class) but q-broadcastness does
    assert b(mk(64, ((1, 8, 512, 512), "bfloat16"))) == \
        b(mk(64, ((1, 1, 512, 512), "bfloat16")))
    assert b(mk(64, ((1, 8, 512, 512), "bfloat16"))) != \
        b(mk(64, ((1, 8, 1, 512), "bfloat16")))
    # batch washes out
    assert b(mk(64, None)) == b(tuning.flash_workload(
        (64, 512, 8, 64), 512, "bfloat16", dropout_on=True,
    ))


def test_tuned_config_validation():
    assert tuning.tuned_flash_blocks(512, 512,
                                     {"block_q": 256, "block_k": 512}) \
        == (256, 512)
    # non-dividing / oversized / misaligned / malformed -> heuristic
    assert tuning.tuned_flash_blocks(384, 512,
                                     {"block_q": 256, "block_k": 512}) is None
    assert tuning.tuned_flash_blocks(512, 512,
                                     {"block_q": 1024, "block_k": 512}) is None
    assert tuning.tuned_flash_blocks(512, 512,
                                     {"block_q": 12, "block_k": 512}) is None
    assert tuning.tuned_flash_blocks(512, 512, {"block_q": 256}) is None
    assert tuning.tuned_flash_blocks(512, 512, "eager") is None
    assert tuning.tuned_q_blk(128, {"q_blk": 32}) == 32
    assert tuning.tuned_q_blk(128, {"q_blk": 48}) is None
    assert tuning.tuned_q_blk(128, {"q_blk": 256}) is None
    assert tuning.tuned_q_blk(128, None) is None


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def _evo_arrays(rng):
    x = jnp.asarray(rng.randn(1, 16, 4, 128, 128).astype(np.float32))
    mask = jnp.asarray(
        (rng.rand(1, 16, 1, 1, 128) > 0.1).astype(np.float32) * -1e9
    )
    bias = jnp.asarray(rng.randn(1, 1, 4, 128, 128).astype(np.float32))
    return x, mask, bias


def test_eager_crossover_dispatch(tune_env, monkeypatch, rng):
    """A cached "eager" verdict must route AUTO dispatch around the
    kernel entirely — the kernel implementation is never consulted."""
    import importlib

    sd_mod = importlib.import_module("unicore_tpu.ops.softmax_dropout")

    x, mask, bias = _evo_arrays(rng)
    wl = tuning.sd_workload(
        x.shape, x.dtype.name,
        mask=(mask.shape, mask.dtype.name), bias=(bias.shape, bias.dtype.name),
        dropout_on=False,
    )
    key = bucket_key(candidates.OPS["softmax_dropout"].bucket(wl))
    tune_env.record(key, "eager")

    monkeypatch.setattr(sd_mod, "use_pallas", lambda: True)

    def boom(*a, **k):
        raise AssertionError("kernel path taken despite eager verdict")

    import unicore_tpu.ops.pallas.softmax_dropout as pl_sd

    monkeypatch.setattr(pl_sd, "softmax_dropout", boom)
    out = ops.softmax_dropout(x, 0.0, is_training=False, mask=mask, bias=bias)
    ref = ops.softmax_dropout_reference(
        x, 0.0, is_training=False, mask=mask, bias=bias
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_tuned_q_blk_dispatch(tune_env, monkeypatch, rng):
    """A cached row-block config must reach the Pallas impl as q_blk."""
    import importlib

    sd_mod = importlib.import_module("unicore_tpu.ops.softmax_dropout")

    x, mask, bias = _evo_arrays(rng)
    wl = tuning.sd_workload(
        x.shape, x.dtype.name,
        mask=(mask.shape, mask.dtype.name), bias=(bias.shape, bias.dtype.name),
        dropout_on=False,
    )
    key = bucket_key(candidates.OPS["softmax_dropout"].bucket(wl))
    tune_env.record(key, {"q_blk": 32})

    monkeypatch.setattr(sd_mod, "use_pallas", lambda: True)
    import unicore_tpu.ops.pallas.softmax_dropout as pl_sd

    seen = {}
    real = pl_sd.softmax_dropout

    def spy(*a, **kw):
        seen["q_blk"] = kw.get("q_blk")
        return real(*a, **kw)

    monkeypatch.setattr(pl_sd, "softmax_dropout", spy)
    out = ops.softmax_dropout(x, 0.0, is_training=False, mask=mask, bias=bias)
    assert seen["q_blk"] == 32
    ref = ops.softmax_dropout_reference(
        x, 0.0, is_training=False, mask=mask, bias=bias
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_inapplicable_tuned_q_blk_falls_to_heuristic_path(tune_env,
                                                          monkeypatch, rng):
    """A cached config whose q_blk doesn't validate for the actual row
    count was never measured as-lowered: dispatch must fall through to
    the heuristic path (which gates this small-work shape to eager),
    not trust the verdict with substitute blocks."""
    import importlib

    sd_mod = importlib.import_module("unicore_tpu.ops.softmax_dropout")

    x = jnp.asarray(rng.randn(1, 4, 96, 128).astype(np.float32))
    wl = tuning.sd_workload(x.shape, x.dtype.name, dropout_on=False)
    key = bucket_key(candidates.OPS["softmax_dropout"].bucket(wl))
    tune_env.record(key, {"q_blk": 128})  # 128 > 96 rows: inapplicable

    monkeypatch.setattr(sd_mod, "use_pallas", lambda: True)
    import unicore_tpu.ops.pallas.softmax_dropout as pl_sd

    def boom(*a, **k):
        raise AssertionError("kernel lowered on an unmeasured config")

    monkeypatch.setattr(pl_sd, "softmax_dropout", boom)
    out = ops.softmax_dropout(x, 0.0, is_training=False)
    ref = ops.softmax_dropout_reference(x, 0.0, is_training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_real_tune_retimes_dry_entries(tune_env):
    """A dry (fake-timing) entry never serves dispatch, so a REAL tune
    run must re-time the bucket instead of 'reusing' it."""
    wl = tuning.ln_workload(8, 128, "float32")
    spec = candidates.OPS["layer_norm"]
    s1, key, e1 = tune_bucket(spec, wl, tune_env,
                              timer=lambda k, c: 1.0)
    assert s1 == "timed" and e1["source"] == "dry"
    # dry rerun reuses (the CI zero-re-timings check)...
    s2, _, _ = tune_bucket(spec, wl, tune_env, timer=lambda k, c: 1.0)
    assert s2 == "reused"
    # ...but a real (device-timed) run does not
    s3, _, e3 = tune_bucket(spec, wl, tune_env)
    assert s3 == "timed" and e3["source"] == "timed"


def test_pallas_sd_explicit_q_blk_matches_reference(rng):
    """The q_blk override changes tiling only, never numerics (dropout
    off: the grid-derived seed layout differs by block size, which is
    why probe keys and fwd/bwd must share one q_blk)."""
    from unicore_tpu.ops.pallas import softmax_dropout as pl_sd

    x = jnp.asarray(rng.randn(2, 4, 64, 128).astype(np.float32))
    ref = ops.softmax_dropout_reference(x, 0.0, is_training=False)
    for blk in (8, 16, 64, None, 999):  # 999 is invalid -> heuristic
        out = pl_sd.softmax_dropout(x, 0.0, is_training=False, q_blk=blk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)


def test_flash_picked_blocks_honor_cache(tune_env):
    from unicore_tpu.ops.pallas import flash_attention as fa

    wl = tuning.flash_workload((1, 256, 1, 64), 256, "float32")
    key = bucket_key(candidates.OPS["flash_attention"].bucket(wl))
    tune_env.record(key, {"block_q": 128, "block_k": 128})
    got = fa.picked_blocks(256, 256, dtype=jnp.float32, d=64)
    assert got == (128, 128)
    # same shapes WITHOUT the tuner info kwargs -> heuristic (no crash)
    assert fa.picked_blocks(256, 256) == fa._pick_blocks(256, 256, 0)


def test_flash_tuned_blocks_parity(tune_env, rng):
    """A tuned block pair must lower (interpret mode here) and produce
    the same numerics as the reference — fwd and bwd trace the same
    memoized decision, so grads stay consistent."""
    from unicore_tpu.ops.pallas.flash_attention import flash_attention

    wl = tuning.flash_workload((2, 256, 2, 64), 256, "float32")
    key = bucket_key(candidates.OPS["flash_attention"].bucket(wl))
    tune_env.record(key, {"block_q": 128, "block_k": 128})

    q = jnp.asarray(rng.randn(2, 256, 2, 64).astype(np.float32))

    def fl(q_):
        return jnp.sum(flash_attention(q_, q_, q_, is_training=False) ** 2)

    def ref(q_):
        qt = jnp.einsum("bqhd->bhqd", q_)
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, qt) * (64 ** -0.5)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bqhd", p, qt) ** 2)

    o1, g1 = jax.value_and_grad(fl)(q)
    o2, g2 = jax.value_and_grad(ref)(q)
    np.testing.assert_allclose(float(o1), float(o2), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=2e-3)


def test_flash_decision_memoized_for_fwd_bwd_agreement(tune_env):
    """The first consult freezes the decision: a cache write between
    the forward and backward trace of one custom_vjp must not flip the
    block choice (dropout mask layouts are grid-dependent)."""
    from unicore_tpu.ops.pallas import flash_attention as fa

    wl = tuning.flash_workload((1, 256, 1, 64), 256, "float32")
    key = bucket_key(candidates.OPS["flash_attention"].bucket(wl))
    heur = fa.picked_blocks(256, 256, dtype=jnp.float32, d=64)
    tune_env.record(key, {"block_q": 128, "block_k": 128})
    # memoized at first consult -> still the heuristic pair
    assert fa.picked_blocks(256, 256, dtype=jnp.float32, d=64) == heur
    tuning.reset_memo()
    assert fa.picked_blocks(256, 256, dtype=jnp.float32, d=64) == (128, 128)


def test_dispatch_report_names_the_path_taken(tune_env):
    """There is no compile probe behind a dispatch site: the path taken
    is the path compiled, and ``dispatch_report`` names it — the kernel
    under a forced backend, the reference once the tuner has recorded
    "eager" for the bucket."""
    from unicore_tpu.ops import backend, softmax_dropout

    x = jnp.zeros((2, 512, 128), jnp.float32)
    desc = "x(2, 512, 128) float32 mask=None bias=None dropout=False"

    def path():
        softmax_dropout(x, 0.0, is_training=False)
        return backend.dispatch_report()["softmax_dropout"][desc]

    with backend.kernel_backend("pallas"):
        assert path() == "pallas"
    # the report is a copy: editing it does not edit the record
    backend.dispatch_report()["softmax_dropout"][desc] = "edited"
    assert backend.dispatch_report()["softmax_dropout"][desc] == "pallas"

    wl = tuning.sd_workload(x.shape, "float32", dropout_on=False)
    tune_env.record(
        bucket_key(candidates.OPS["softmax_dropout"].bucket(wl)), "eager")
    tuning.reset_memo()
    orig = backend._on_tpu
    backend._on_tpu = lambda: True  # auto backend, as on the chip
    try:
        assert path() == "reference"
    finally:
        backend._on_tpu = orig


def test_off_mode_ignores_cache(tune_env):
    wl = tuning.sd_workload((2, 64, 128), "float32", dropout_on=False)
    key = bucket_key(candidates.OPS["softmax_dropout"].bucket(wl))
    tune_env.record(key, "eager")
    tuning.set_autotune_mode("off")
    assert tuning.softmax_dropout_decision(
        (2, 64, 128), "float32", dropout_on=False
    ) is None
    tuning.set_autotune_mode("cache")
    tuning.reset_memo()
    assert tuning.softmax_dropout_decision(
        (2, 64, 128), "float32", dropout_on=False
    ) == "eager"


def test_heuristic_crossover_gate(rng):
    """Satellite: the no-cache default must not lower a kernel slower
    than eager for small-row/batched-bias shapes (the r5-era
    evoformer case) while keeping the shapes where the kernel wins."""
    from unicore_tpu.ops.softmax_dropout import _heuristic_kernel_win

    # evoformer: 5-D, batched mask, 128-row/128-k -> tiny per-program work
    xe = jnp.zeros((1, 128, 4, 128, 128), jnp.bfloat16)
    me = jnp.zeros((1, 128, 1, 1, 128), jnp.bfloat16)
    be = jnp.zeros((1, 1, 4, 128, 128), jnp.bfloat16)
    assert not _heuristic_kernel_win(xe, me, be)
    # BERT shape: wins (r5-era record, deleted: 1.134x)
    xb = jnp.zeros((32, 12, 512, 512), jnp.bfloat16)
    bb = jnp.zeros((1, 12, 512, 512), jnp.bfloat16)
    assert _heuristic_kernel_win(xb, None, bb)
    # long-k rows: wins (r5-era record, deleted: 1.108x)
    xk = jnp.zeros((4, 8, 1024, 2048), jnp.bfloat16)
    bk = jnp.zeros((1, 8, 1024, 2048), jnp.bfloat16)
    assert _heuristic_kernel_win(xk, None, bk)


# ---------------------------------------------------------------------------
# tuner (interpret mode, fixed fake timings)
# ---------------------------------------------------------------------------


def _fixed_timer(timings):
    def timer(key, config):
        return timings[candidates.describe_config(config)]

    return timer


def test_tuner_picks_fastest_kernel_config(tune_env):
    wl = tuning.sd_workload((1, 64, 128), "float32",
                            dropout_on=False)
    spec = candidates.OPS["softmax_dropout"]
    names = [candidates.describe_config(c) for c in spec.candidates(wl)]
    timings = {n: 100.0 for n in names}
    timings["eager"] = 50.0
    timings["q_blk=16"] = 10.0  # clear winner, beats eager x margin
    status, key, entry = tune_bucket(
        spec, wl, tune_env, timer=_fixed_timer(timings)
    )
    assert status == "timed"
    assert entry["winner"] == {"q_blk": 16}
    assert entry["source"] == "dry"
    # identical timings -> identical pick (determinism), and the entry
    # is REUSED: zero re-timings on the second invocation
    status2, _, entry2 = tune_bucket(
        spec, wl, tune_env, timer=_fixed_timer(timings)
    )
    assert status2 == "reused" and entry2["winner"] == {"q_blk": 16}


def test_tuner_eager_crossover_and_margin(tune_env):
    """Eager wins the bucket when no kernel config beats it by the
    noise margin — a tie routed to the kernel is downside-only."""
    wl = tuning.sd_workload((1, 64, 128), "float32", dropout_on=False)
    spec = candidates.OPS["softmax_dropout"]
    names = [candidates.describe_config(c) for c in spec.candidates(wl)]
    timings = {n: 100.0 for n in names}
    timings["eager"] = 100.0  # every kernel config merely ties
    _, _, entry = tune_bucket(spec, wl, tune_env,
                              timer=_fixed_timer(timings), force=True)
    assert entry["winner"] == "eager"


def test_tune_workloads_dry_run_deterministic(tmp_path):
    """The CI plumbing check: dry-run over presets is deterministic and
    the second run reuses every entry."""
    cache = TuneCache(paths=[str(tmp_path / "c.json")], fingerprint="fpX")
    wls = [
        tuning.sd_workload((1, 4, 64, 128), "float32", dropout_on=False),
        tuning.ln_workload(64, 128, "float32"),
    ]
    r1 = tune_workloads(wls, cache, dry_run=True)
    assert r1["timed"] == 2 and r1["reused"] == 0
    winners1 = {k: v["winner"] for k, v in r1["entries"].items()}
    # layer_norm has exactly one candidate: eager by walkover
    assert winners1[[k for k in winners1 if k.startswith("layer_norm")][0]] \
        == "eager"
    cache2 = TuneCache(paths=[str(tmp_path / "c.json")], fingerprint="fpX")
    r2 = tune_workloads(wls, cache2, dry_run=True)
    assert r2["timed"] == 0 and r2["reused"] == 2
    assert {k: v["winner"] for k, v in r2["entries"].items()} == winners1


def test_sd_shrink_preserves_patterns_and_bucket():
    """The dry-run shrink must not flip broadcast patterns: shrunk and
    full workloads lower the same BlockSpec variants and record under
    the same bucket key."""
    for name in ("sd_evoformer", "sd_bert", "sd_k2048"):
        wl = tuning.PRESETS[name]
        spec = candidates.OPS[wl["op"]]
        assert spec.bucket(spec.shrink(wl)) == spec.bucket(wl), name


def test_cli_dry_run_defaults_away_from_overlay(tmp_path, monkeypatch):
    """unicore_tune tune --dry-run without --cache must not write fake
    timings into the user overlay."""
    from unicore_tpu.ops.tuning import cache as cache_mod
    from unicore_tpu.ops.tuning.cli import main

    overlay_dir = tmp_path / "overlay"
    monkeypatch.setenv("UNICORE_TPU_CACHE_DIR", str(overlay_dir))
    assert main(["tune", "--dry-run", "--workloads", "layer_norm_bert",
                 "-q"]) == 0
    assert not (overlay_dir / "kernel_tune_cache.json").exists()
    assert cache_mod.overlay_cache_path().startswith(str(overlay_dir))


def test_lookup_only_consults_never_tune(tune_env, monkeypatch):
    """picked_blocks-style consults (allow_tune unset) must not trigger
    tune-mode timing — their synthesized workloads carry degenerate
    batch/head extents."""
    tuning.set_autotune_mode("tune")
    monkeypatch.setattr(tuning, "_can_tune_here", lambda: True)
    called = []

    def boom(*a, **k):
        called.append(a)
        raise AssertionError("tuned from a lookup-only consult")

    import unicore_tpu.ops.tuning.tuner as tuner_mod

    monkeypatch.setattr(tuner_mod, "tune_bucket", boom)
    assert tuning.flash_decision((1, 256, 1, 64), 256, "float32") is None
    assert not called


def test_forced_config_context(tune_env):
    with tuning.forced_config("flash_attention",
                              {"block_q": 128, "block_k": 128}):
        d = tuning.flash_decision((1, 256, 1, 64), 256, "float32")
        assert d == {"block_q": 128, "block_k": 128}
    assert tuning.flash_decision((1, 256, 1, 64), 256, "float32") is None


# ---------------------------------------------------------------------------
# fused chunked linear+cross-entropy (ISSUE 10)
# ---------------------------------------------------------------------------


def test_ce_bucket_rounds_rows_vocab_keeps_hidden():
    b = candidates.OPS["fused_cross_entropy"].bucket
    # rows/vocab pow2-bucket (8192 covers 8000), hidden stays exact
    assert b(tuning.ce_workload(8000, 768, 30528, "bfloat16")) == \
        b(tuning.ce_workload(8192, 768, 32768, "bfloat16"))
    assert b(tuning.ce_workload(8192, 768, 30528, "bfloat16")) != \
        b(tuning.ce_workload(8192, 1024, 30528, "bfloat16"))
    assert b(tuning.ce_workload(8192, 768, 30528, "bfloat16", tied=False)) \
        != b(tuning.ce_workload(8192, 768, 30528, "bfloat16", tied=True))


def test_ce_candidates_eager_always_chunks_bounded():
    wl = tuning.ce_workload(8192, 768, 30528, "bfloat16")
    cands = candidates.OPS["fused_cross_entropy"].candidates(wl)
    assert cands[0] == "eager"
    chunks = [c["chunk"] for c in cands[1:]]
    assert chunks and all(1 <= c <= wl["rows"] for c in chunks)
    assert len(set(chunks)) == len(chunks)
    # the op's own heuristic pick is always in the running
    from unicore_tpu.ops.fused_cross_entropy import pick_chunk

    assert pick_chunk(wl["rows"], wl["vocab"]) in chunks


def test_tuned_ce_chunk_validation():
    assert tuning.tuned_ce_chunk(1024, {"chunk": 256}) == 256
    assert tuning.tuned_ce_chunk(128, {"chunk": 256}) == 128  # clamped
    assert tuning.tuned_ce_chunk(1024, {"chunk": 0}) is None
    assert tuning.tuned_ce_chunk(1024, "eager") is None
    assert tuning.tuned_ce_chunk(1024, None) is None
    assert tuning.tuned_ce_chunk(1024, {"q_blk": 64}) is None


def test_ce_cached_verdicts_steer_dispatch(tune_env):
    """A cached {"chunk": n} reaches the op's chunk resolution; a cached
    "eager" retires the fused path for the bucket."""
    from unicore_tpu.ops import fused_cross_entropy as fce

    rows, hidden, vocab = 4096, 64, 512
    wl = tuning.ce_workload(rows, hidden, vocab, "float32")
    key = bucket_key(candidates.OPS["fused_cross_entropy"].bucket(wl))

    tune_env.record(key, {"chunk": 96})
    tuning.reset_memo()
    assert fce._resolve_chunk(rows, hidden, vocab, "float32", True,
                              True) == 96
    tune_env.record(key, "eager")
    tuning.reset_memo()
    assert fce._resolve_chunk(rows, hidden, vocab, "float32", True,
                              True) is None
    # a miss past FUSE_MIN_BYTES falls to the byte heuristic (vocab
    # 8192 -> chunk 1024 < rows, a genuinely chunkable bucket)
    other = tuning.ce_workload(rows, hidden, 8192, "float32")
    assert bucket_key(
        candidates.OPS["fused_cross_entropy"].bucket(other)) != key
    assert fce._resolve_chunk(rows, hidden, 8192, "float32", True, True) \
        == fce.pick_chunk(rows, 8192)


def test_ce_runner_builds_fused_and_eager(tune_env):
    """Both candidate runners AOT-compile (the dry-run path CI walks)."""
    wl = candidates.OPS["fused_cross_entropy"].shrink(
        tuning.PRESETS["fused_ce_bert"]
    )
    for config in ("eager", {"chunk": 64}):
        fn = candidates.OPS["fused_cross_entropy"].build_runner(wl, config)
        out = fn()
        assert all(np.all(np.isfinite(np.asarray(x))) for x in out)


def test_evoformer_static_verdict_out_of_the_box(tune_env):
    """The r5-era evoformer bucket (~0.99x kernel-vs-eager) carries a
    committed "eager" verdict: with an EMPTY cache, dispatch must route
    to eager for both dropout states — and a measured cache entry must
    still override the static verdict."""
    mask = ((1, 128, 1, 1, 128), "bfloat16")
    bias = ((1, 1, 4, 128, 128), "bfloat16")
    for dropout_on in (True, False):
        assert tuning.softmax_dropout_decision(
            (1, 128, 4, 128, 128), "bfloat16", mask=mask, bias=bias,
            dropout_on=dropout_on,
        ) == "eager"
    # a different (winning) bucket stays on the heuristics
    assert tuning.softmax_dropout_decision(
        (32, 12, 512, 512), "bfloat16",
        bias=((1, 12, 512, 512), "bfloat16"), dropout_on=True,
    ) is None
    wl = tuning.sd_workload(
        (1, 128, 4, 128, 128), "bfloat16", mask=mask, bias=bias,
        dropout_on=True,
    )
    key = bucket_key(candidates.OPS["softmax_dropout"].bucket(wl))
    assert key in tuning.STATIC_VERDICTS
    tune_env.record(key, {"q_blk": 128})
    tuning.reset_memo()
    assert tuning.softmax_dropout_decision(
        (1, 128, 4, 128, 128), "bfloat16", mask=mask, bias=bias,
        dropout_on=True,
    ) == {"q_blk": 128}


def test_cli_dry_run_roundtrip(tmp_path, capsys):
    """End-to-end CLI: tune --dry-run twice against one cache file; the
    second report shows zero re-timings; `cache` mode reads it back."""
    import json

    from unicore_tpu.ops.tuning.cli import main

    cache = str(tmp_path / "cli_cache.json")
    rep1, rep2 = str(tmp_path / "r1.json"), str(tmp_path / "r2.json")
    args = ["tune", "--dry-run", "--cache", cache,
            "--workloads", "sd_evoformer,layer_norm_bert", "-q"]
    assert main(args + ["--json", rep1]) == 0
    assert main(args + ["--json", rep2]) == 0
    r1, r2 = json.load(open(rep1)), json.load(open(rep2))
    assert r1["timed"] == 2 and r1["reused"] == 0
    assert r2["timed"] == 0 and r2["reused"] == 2
    assert {k: v["winner"] for k, v in r1["entries"].items()} == \
        {k: v["winner"] for k, v in r2["entries"].items()}
    assert main(["cache", "--cache", cache, "-q"]) == 0


# ---------------------------------------------------------------------
# optim_sr_cast (ISSUE 15: bf16-moment SR re-quantization)
# ---------------------------------------------------------------------

def test_sr_cast_bucket_and_candidates():
    b = candidates.OPS["optim_sr_cast"].bucket
    # one entry covers a pow2 family of leaf sizes
    assert b(tuning.sr_cast_workload(500_000)) == \
        b(tuning.sr_cast_workload(524_288))
    assert b(tuning.sr_cast_workload(524_288)) != \
        b(tuning.sr_cast_workload(1_048_576))
    wl = tuning.sr_cast_workload(768 * 768)
    cands = candidates.OPS["optim_sr_cast"].candidates(wl)
    assert cands[0] == "eager" and {"impl": "pallas"} in cands
    # dry-run shrink keeps the workload well-formed and small
    small = candidates.OPS["optim_sr_cast"].shrink(wl)
    assert small["n"] <= 4096 and small["op"] == "optim_sr_cast"
    assert "optim_sr_cast_moments" in tuning.PRESETS


def test_sr_cast_runner_builds_both_candidates(tune_env):
    """Both candidate runners AOT-compile and preserve value brackets:
    every output sits within one bf16 ulp of the input (the two impls
    draw different random streams, so PARITY here is the rounding
    contract, not bitwise equality)."""
    wl = candidates.OPS["optim_sr_cast"].shrink(
        tuning.PRESETS["optim_sr_cast_moments"]
    )
    for config in ("eager", {"impl": "pallas"}):
        fn = candidates.OPS["optim_sr_cast"].build_runner(wl, config)
        out = np.asarray(fn(), np.float64)
        assert out.size == wl["n"] or out.size >= wl["n"]
        assert np.all(np.isfinite(out))


def test_sr_cast_cached_verdict_steers_dispatch(tune_env, rng):
    """A cached "eager" verdict must route ops.fp32_to_bf16_sr to the
    threefry reference even when the pallas backend is forced."""
    import jax

    from unicore_tpu.ops import backend as ops_backend
    from unicore_tpu.ops.rounding import (
        fp32_to_bf16_sr,
        fp32_to_bf16_sr_reference,
    )

    x = jnp.asarray(rng.randn(2048), jnp.float32)
    key = jax.random.PRNGKey(3)
    wl = tuning.sr_cast_workload(x.size)
    bucket = bucket_key(candidates.OPS["optim_sr_cast"].bucket(wl))
    tune_env.record(bucket, "eager")
    tuning.reset_memo()
    prev = ops_backend.get_kernel_backend()
    try:
        ops_backend.set_kernel_backend("pallas")
        got = fp32_to_bf16_sr(x, key)
    finally:
        ops_backend.set_kernel_backend(prev)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(fp32_to_bf16_sr_reference(x, key), np.float32),
    )


def test_sr_cast_decision_never_raises(tune_env):
    # with an empty cache every size falls through to the heuristics
    # (None); odd sizes must never raise out of the dispatch consult
    for n in (1, 7, 1023, 768 * 768, 10 ** 9):
        assert tuning.sr_cast_decision(n) is None
