#!/usr/bin/env python3
"""Chip smoke: the trainer and the serve engine, once, on the TPU.

    python chip_smoke.py             # one chip: train, kernel and serve phases
    python chip_smoke.py --chips 4   # four chips: the mesh phase, only

Drives the system through the entry points a user calls
(``unicore_tpu_cli.train.cli_main``, ``unicore_tpu.serve.cli.main``), in
this one process — a chip belongs to one process — at the published
BERT-base width and the ``transformer_lm_base`` width, on seeded
synthetic data and seeded random weights.  Every phase prints one JSON
line (also appended to ``chiprun_out/chip_smoke.jsonl``); a failed check
exits non-zero at once; the last line of a run that passed is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

With no TPU it exits non-zero and runs nothing.  The phase functions
take their sizes as an argument and check only what holds on any
backend, so ``tests/test_chip_smoke.py`` rehearses them at tiny widths on
the CPU; what only a chip can show (a ``tpu`` device, a Pallas kernel in
the compiled text, memory statistics, cache hits) is checked in ``main``.
"""

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

WORK = os.path.join(REPO, ".chip_smoke")
REPORT = os.path.join(REPO, "chiprun_out", "chip_smoke.jsonl")

# BERT-base as published (12 x 768 x 3072, 12 heads, 30,522 word pieces:
# 4 specials + 30,517 symbols + [MASK]), seq 512, global batch 32
BERT = dict(
    layers=12, dim=768, ffn=3072, heads=12, seq=512, batch=32,
    symbols=30517, updates=6, save_at=3, mesh_updates=3,
)
# transformer_lm_base (12 x 768, 12 heads, head dim 64), served from a
# checkpoint of 3 updates
LM = dict(
    layers=12, dim=768, ffn=3072, heads=12, seq=512, batch=8,
    symbols=8188, updates=3,
    page_size=64, num_pages=64, max_batch=8, prefill_chunk=64,
    max_new_tokens=8, prefix_len=128,
    # mixed prompt lengths, three of them longer than the prefill chunk
    prompt_lens=(5, 17, 33, 40, 64, 70, 100, 150),
)
# the ragged kernel against its reference over ROW PATTERNS, at the shapes
# two serve configurations hand it: opt_1.3b's 32 heads x 64 over a
# float32 pool (widths 1 and 128) and the latent step's one head of 640
# lanes under 128 query heads (16 rows x 64 cut into 256 tiles of 4 tokens)
KERNEL = dict(
    page_size=64, heads=32, head_dim=64, rows=208, table_pages=32,
    num_pages=128, widths=(1, 128), pages_per_block=(1, 2, 4),
    patterns=("zeros_first", "zeros_last", "zeros_between", "zeros_200",
              "one_block", "odd_even", "partial_last"),
    latent_lanes=640, latent_heads=128, latent_rows=16, latent_chunk=64,
    latent_tile=4, latent_table_pages=48, latent_num_pages=96,
    # inputs are whole bfloat16 numbers, so one pass on the MXU rounds
    # only the probabilities (2^-9 of values of order one); a slot read
    # stale or early is another page's numbers, or POISON
    gap_limit=2e-2, latent_gap_limit=1e-3,
)
POISON = 1e4
# |loss(variant) - loss(one device)| <= LOSS_RTOL * |loss(one device)| on
# every update: same data, same init, no dropout; what differs is the
# order of bf16 reductions and, under tp, the attention path (einsum in
# place of the flash kernel)
LOSS_RTOL = 2e-2
# an engine token that is not the full forward's argmax must be within
# this much of it in logit: two programs that round differently may
# break an exact tie differently, nothing more
LOGIT_TIE_TOL = 2e-2


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(phase, **fields):
    line = json.dumps({"phase": phase, **fields}, default=str)
    print(line, flush=True)
    os.makedirs(os.path.dirname(REPORT), exist_ok=True)
    with open(REPORT, "a") as f:
        f.write(line + "\n")


# -- compile accounting ------------------------------------------------

class _Compiles:
    """jax's own monitoring events: persistent-cache hits and the
    seconds of every backend compile."""

    def __init__(self):
        self.hits = 0
        self.seconds = []

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds.append(duration)


_COMPILES = None


def compiles():
    global _COMPILES
    if _COMPILES is None:
        import jax

        _COMPILES = _Compiles()
        jax.monitoring.register_event_listener(_COMPILES.on_event)
        jax.monitoring.register_event_duration_secs_listener(
            _COMPILES.on_duration)
    return _COMPILES


# -- data --------------------------------------------------------------

def write_corpus(data_dir, seed, symbols, seq, n_train, n_valid):
    """A seeded corpus in the repo's own format: ``dict.txt`` plus
    ``{train,valid}.rec`` of token lists, Zipf-distributed, most of them
    as long as the sequence allows."""
    import numpy as np

    from unicore_tpu.data import IndexedRecordWriter

    os.makedirs(data_dir, exist_ok=True)
    words = ["w%d" % i for i in range(symbols)]
    with open(os.path.join(data_dir, "dict.txt"), "w") as f:
        for i, w in enumerate(words):
            f.write(f"{w} {symbols - i}\n")
    rng = np.random.RandomState(seed)
    p = 1.0 / np.arange(1, symbols + 1)
    p /= p.sum()
    for split, n in (("train", n_train), ("valid", n_valid)):
        with IndexedRecordWriter(os.path.join(data_dir, split + ".rec")) as w:
            for _ in range(n):
                length = int(rng.randint(max(2, seq // 2), seq - 1))
                w.write([words[i] for i in rng.choice(symbols, length, p=p)])
    return data_dir


# -- the train entry point, in process ---------------------------------

def _import_example(name):
    """``--user-dir examples/<name>`` imports the example under its bare
    name, the serve loader imports it as ``examples.<name>``.  In one
    process both must be ONE module, or its ``@register_*`` run twice."""
    mod = importlib.import_module(f"examples.{name}")
    sys.modules.setdefault(name, mod)


@contextlib.contextmanager
def recording_trainer():
    """Let ``cli_main`` build its Trainer as always, and keep hold of
    it: the per-update losses, the compiled step and the state are what
    the checks read."""
    import unicore_tpu_cli.train as train_cli

    made = []

    class Recording(train_cli.Trainer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.smoke_losses = []
            made.append(self)

        def _reduce_and_log_stats(self, *a, **kw):
            out = super()._reduce_and_log_stats(*a, **kw)
            self.smoke_losses.append(float(out["loss"]))
            return out

    orig = train_cli.Trainer
    train_cli.Trainer = Recording
    try:
        yield made
    finally:
        train_cli.Trainer = orig


def run_train_cli(argv):
    """``unicore-train <argv>`` in this process; returns its Trainer."""
    import unicore_tpu_cli.train as train_cli

    saved = sys.argv
    sys.argv = ["unicore-train"] + [str(a) for a in argv]
    try:
        with recording_trainer() as made:
            train_cli.cli_main()
    finally:
        sys.argv = saved
    check(len(made) == 1, f"cli_main built {len(made)} trainers")
    return made[0]


def _common_train_args(data_dir, save_dir, size, updates):
    return [
        data_dir, "--valid-subset", "valid", "--num-workers", "0",
        "--optimizer", "adam", "--adam-betas", "(0.9, 0.98)",
        "--adam-eps", "1e-6", "--clip-norm", "1.0",
        "--lr-scheduler", "polynomial_decay", "--lr", "1e-4",
        "--warmup-updates", "2", "--total-num-update", "1000",
        "--batch-size", size["batch"], "--max-seq-len", size["seq"],
        "--required-batch-size-multiple", "1",
        "--update-freq", "1", "--seed", "1", "--bf16",
        "--max-update", updates, "--log-interval", "1",
        "--log-format", "simple", "--no-progress-bar",
        "--no-epoch-checkpoints", "--no-last-checkpoints",
        "--save-dir", save_dir,
        "--tmp-save-dir", save_dir + "_tmp",
    ]


def _bert_args(data_dir, save_dir, size, updates):
    return _common_train_args(data_dir, save_dir, size, updates) + [
        "--user-dir", os.path.join(REPO, "examples", "bert"),
        "--task", "bert", "--loss", "masked_lm", "--arch", "bert_base",
        "--pre-tokenized",
        "--encoder-layers", size["layers"],
        "--encoder-embed-dim", size["dim"],
        "--encoder-ffn-embed-dim", size["ffn"],
        "--encoder-attention-heads", size["heads"],
    ]


def _step_report(trainer):
    """What the compiled train step and the devices say."""
    import jax

    text = trainer._compiled_train_step.as_text()
    leaf = jax.tree_util.tree_leaves(trainer.state["params"])[0]
    return {
        "platform": sorted({d.platform for d in leaf.devices()}),
        "mesh": dict(zip(trainer.mesh.axis_names,
                         trainer.mesh.devices.shape)),
        "tpu_custom_call": text.count("tpu_custom_call"),
        "all_reduce": text.count("all-reduce"),
        "memory_analysis_gb": trainer._memory_analysis,
        "bytes_in_use": [
            (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.local_devices()
        ],
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()
        ],
    }


def _check_losses(losses, updates, what):
    import math

    check(len(losses) >= updates,
          f"{what}: {len(losses)} updates logged, wanted {updates}")
    check(all(math.isfinite(x) for x in losses),
          f"{what}: non-finite loss in {losses}")
    check(len(set(losses)) > 1, f"{what}: loss is constant: {losses}")


# -- phase: train ------------------------------------------------------

def train_phase(work, seed, size):
    """BERT MLM through ``unicore-train``: updates, one interval
    checkpoint, then the compile cache: the step is compiled again after
    ``jax.clear_caches()`` and the cache's own events say where it came
    from."""
    import jax

    from unicore_tpu.distributed import utils as dist_utils
    from unicore_tpu.ops import backend

    _import_example("bert")
    dist_utils.reset_mesh()
    data = write_corpus(
        os.path.join(work, "bert_data"), seed, size["symbols"],
        size["seq"], n_train=size["batch"] * (size["updates"] + 2),
        n_valid=size["batch"],
    )
    save_dir = os.path.join(work, "bert_ckpt")
    cc = compiles()
    hits_start, n_start = cc.hits, len(cc.seconds)
    t0 = time.perf_counter()
    trainer = run_train_cli(
        _bert_args(data, save_dir, size, size["updates"]) + [
            "--save-interval-updates", size["save_at"],
            "--disable-validation",
        ])
    wall = time.perf_counter() - t0
    first_compile = max(cc.seconds[n_start:])
    _check_losses(trainer.smoke_losses, size["updates"], "bert")
    ckpts = sorted(f for f in os.listdir(save_dir) if f.endswith(".pt"))
    check(any(f"_{size['save_at']}.pt" in f for f in ckpts),
          f"no checkpoint of update {size['save_at']} in {ckpts}")

    # The same step compiled again from nothing but the persistent
    # cache: a second, one-update run through the SAME entry point after
    # jax.clear_caches().  Lowering the step from here, from abstract
    # arguments, was a miss on the chip (PERF.md, PR 21): a Mosaic
    # kernel's payload embeds the Python frames it was traced under, so
    # a program that holds one can key differently by another path.
    report = _step_report(trainer)
    losses, updates = trainer.smoke_losses, trainer.get_num_updates()
    del trainer
    gc.collect()
    hits0, n0 = cc.hits, len(cc.seconds)
    jax.clear_caches()
    t0 = time.perf_counter()
    again = run_train_cli(
        _bert_args(data, save_dir + "_again", size, 1)
        + ["--no-save", "--disable-validation"])
    recompile_wall = time.perf_counter() - t0
    check(again.get_num_updates() == 1, "the second run took no update")
    del again
    gc.collect()
    cache_dir = jax.config.jax_compilation_cache_dir
    report.update({
        "updates": updates,
        "losses": losses,
        "checkpoints": ckpts,
        "wall_s": round(wall, 1),
        "first_compile_s": first_compile,
        "first_run_cache_hits": hits0 - hits_start,
        "second_run_wall_s": round(recompile_wall, 1),
        # the three longest compiles of the second run
        "second_run_compile_s": sorted(
            round(x, 2) for x in cc.seconds[n0:])[-3:],
        "second_run_cache_hits": cc.hits - hits0,
        "cache_dir": cache_dir,
        "cache_entries": (len(os.listdir(cache_dir))
                          if cache_dir and os.path.isdir(cache_dir) else 0),
        "kernel_dispatch": backend.dispatch_report(),
    })
    shutil.rmtree(save_dir)  # gigabytes at the real width
    return report


# -- phase: serve ------------------------------------------------------

def _prompts(seed, size, vocab):
    """Mixed-length prompts; the first and the last share a prefix of
    whole pages.  The last is admitted only when a slot frees (there
    are more requests than ``max_batch``), by which time the first has
    registered the prefix."""
    import numpy as np

    rng = np.random.RandomState(seed + 1)

    def rnd(n):
        return [int(t) for t in rng.randint(4, vocab, size=(n,))]

    prefix = rnd(size["prefix_len"])
    prompts = [prefix + rnd(7)]
    prompts += [rnd(n) for n in size["prompt_lens"]]
    prompts.append(prefix + rnd(11))
    return prompts


def _teacher_forced(model, params, prompt, tokens, pad_to, pad):
    """The engine-free full forward over prompt + generated tokens: one
    causal pass gives the logits every generated token was the argmax
    of.  Returns (exact matches, worst logit gap of the mismatches)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt) + list(tokens)
    toks = np.full((1, pad_to), pad, np.int32)
    toks[0, :len(seq)] = seq
    logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(toks))
    logits = np.asarray(logits[0].astype(jnp.float32))
    exact, worst = 0, 0.0
    for i, tok in enumerate(tokens):
        row = logits[len(prompt) - 1 + i]
        if int(row.argmax()) == tok:
            exact += 1
        else:
            worst = max(worst, float(row.max() - row[tok]))
    return exact, worst


def serve_phase(work, seed, size):
    """``unicore-train`` on the LM example for a checkpoint, then the
    ``unicore_tpu.serve`` CLI on it: mixed prompts through chunked
    prefill, decode and the prefix cache, and two requests compared
    with the full forward."""
    from unicore_tpu.deploy import load_serve_model
    from unicore_tpu.distributed import utils as dist_utils
    from unicore_tpu.ops import backend
    from unicore_tpu.serve import cli as serve_cli

    _import_example("lm")
    dist_utils.reset_mesh()
    data = write_corpus(
        os.path.join(work, "lm_data"), seed, size["symbols"], size["seq"],
        n_train=size["batch"] * (size["updates"] + 2),
        n_valid=size["batch"],
    )
    save_dir = os.path.join(work, "lm_ckpt")
    trainer = run_train_cli(
        _common_train_args(data, save_dir, size, size["updates"]) + [
            "--user-dir", os.path.join(REPO, "examples", "lm"),
            "--task", "lm", "--loss", "lm_cross_entropy",
            "--arch", "transformer_lm_base",
            "--rotary", "True", "--rel-pos", "False", "--abs-pos", "False",
            "--decoder-layers", size["layers"],
            "--decoder-embed-dim", size["dim"],
            "--decoder-ffn-embed-dim", size["ffn"],
            "--decoder-attention-heads", size["heads"],
            "--save-interval-updates", size["updates"],
            "--disable-validation",
        ])
    _check_losses(trainer.smoke_losses, size["updates"], "lm")
    lm_losses = trainer.smoke_losses
    del trainer
    gc.collect()
    ckpt = os.path.join(save_dir, f"checkpoint_1_{size['updates']}.pt")
    check(os.path.exists(ckpt), f"no {ckpt}")

    dict_path = os.path.join(data, "dict.txt")
    vocab = size["symbols"] + 4
    prompts = _prompts(seed, size, vocab)
    prompts_path = os.path.join(work, "prompts.txt")
    with open(prompts_path, "w") as f:
        for p in prompts:
            f.write(" ".join(map(str, p)) + "\n")
    out_path = os.path.join(work, "serve_report.json")
    t0 = time.perf_counter()
    rc = serve_cli.main([
        "--checkpoint", ckpt, "--dict", dict_path,
        "--prompts", prompts_path, "--json", out_path,
        "--max-new-tokens", str(size["max_new_tokens"]),
        "--page-size", str(size["page_size"]),
        "--num-pages", str(size["num_pages"]),
        "--max-batch", str(size["max_batch"]),
        "--prefill-chunk", str(size["prefill_chunk"]),
    ])
    wall = time.perf_counter() - t0
    check(rc == 0, f"serve CLI returned {rc}")
    with open(out_path) as f:
        rep = json.load(f)
    results, stats = rep["results"], rep["stats"]
    check(len(results) == len(prompts) >= 8,
          f"{len(results)} results for {len(prompts)} requests")
    reasons = sorted({r["finish_reason"] for r in results})
    check(set(reasons) <= {"eos", "length"},
          f"finish reasons {reasons}: wanted eos/length only")
    check(stats["host_faults"] == 0 and stats["quarantined"] == 0
          and stats["shed"] == 0, f"faults in {stats}")
    check(stats["prefix_hits"] >= 1, f"no prefix-cache hit: {stats}")
    check(rep["pool_clean"], "the pool did not end idle")
    widths = rep["kernel_dispatch"].get("ragged_paged_attention", {})
    check(len(widths) >= 2,
          f"attention path recorded for {sorted(widths)}: wanted the "
          "decode width and the prefill-chunk width")

    # two requests against the engine-free full forward: a short one and
    # the prefix-sharing one that was served through the prefix cache
    model, params = load_serve_model(ckpt, dict_path)
    pad_to = -(-(max(map(len, prompts)) + size["max_new_tokens"])
               // 128) * 128
    pad_to = min(pad_to, model.max_seq_len)
    compared = {}
    for idx in (1, len(prompts) - 1):
        r = results[idx]
        exact, worst = _teacher_forced(
            model, params, r["prompt"], r["tokens"], pad_to,
            model.padding_idx)
        compared[r["request_id"]] = {
            "tokens": len(r["tokens"]), "exact": exact,
            "worst_logit_gap": round(worst, 5)}
        check(worst <= LOGIT_TIE_TOL,
              f"{r['request_id']}: an engine token is {worst:.4f} below "
              f"the full forward's argmax (> {LOGIT_TIE_TOL})")
    return {
        "lm_losses": lm_losses,
        "requests": len(results),
        "finish_reasons": reasons,
        "generated_tokens": stats["generated_tokens"],
        "decode_steps": stats["decode_steps"],
        "prefills": stats["prefills"],
        "prefix_hits": stats["prefix_hits"],
        "host_faults": stats["host_faults"],
        "quarantined": stats["quarantined"],
        "wall_s": round(wall, 1),
        "attention_paths": widths,
        "kernel_dispatch": backend.dispatch_report(),
        "vs_full_forward": compared,
    }


# -- phase: mesh (four chips) ------------------------------------------

def mesh_phase(work, seed, size, devices):
    """BERT on one device and on dp / fsdp / tp meshes over ``devices``:
    same seed, same global batch, no dropout.  Losses must agree, every
    device must hold buffers, and the state must really be sharded."""
    import jax

    from unicore_tpu import parallel
    from unicore_tpu.distributed import utils as dist_utils

    _import_example("bert")
    n = len(devices)
    check(list(jax.devices()) == list(devices),
          "the mesh phase takes every device jax sees")
    data = write_corpus(
        os.path.join(work, "bert_data"), seed, size["symbols"],
        size["seq"], n_train=size["batch"] * (size["mesh_updates"] + 2),
        n_valid=size["batch"],
    )
    variants = [
        ("one", devices[:1], []),
        (f"data{n}", devices, []),
        ("fsdp2", devices, ["--fsdp-size", "2"]),
        ("tp2", devices, ["--tensor-parallel-size", "2"]),
    ]
    out = {}
    for name, devs, extra in variants:
        # the mesh over a subset of the devices is built as
        # __graft_entry__ builds it; the Trainer finds it installed
        dist_utils.reset_mesh(dist_utils.get_mesh(None, devices=devs)
                              if len(devs) < n else None)
        trainer = run_train_cli(
            _bert_args(data, os.path.join(work, "mesh_" + name), size,
                       size["mesh_updates"]) + [
                "--no-save", "--disable-validation",
                "--dropout", "0.0", "--emb-dropout", "0.0",
                "--attention-dropout", "0.0",
                "--activation-dropout", "0.0",
            ] + extra)
        _check_losses(trainer.smoke_losses, size["mesh_updates"], name)
        rep = _step_report(trainer)
        rep["losses"] = trainer.smoke_losses
        state = trainer.state
        rep["opt_state_sharded"] = any(
            leaf.ndim >= 1 and not leaf.sharding.is_fully_replicated
            for leaf in jax.tree_util.tree_leaves(state["opt_state"]))
        attn = state["params"]["sentence_encoder"]["layers_0"][
            "self_attn"]["in_proj"]["kernel"]
        rep["attention_kernel_sharded"] = (
            not attn.sharding.is_fully_replicated)
        out[name] = rep
        del trainer, state, attn
        parallel.disable_tensor_parallel()
        dist_utils.reset_mesh()
        gc.collect()

    base = out["one"]["losses"]
    for name, rep in out.items():
        worst = max(abs(a - b) / abs(b)
                    for a, b in zip(rep["losses"], base))
        rep["loss_rel_err_vs_one"] = round(worst, 6)
        check(worst <= LOSS_RTOL,
              f"{name}: loss {rep['losses']} is {worst:.4f} (rel) from "
              f"one device's {base} (> {LOSS_RTOL})")
    check(out["one"]["mesh"]["data"] == 1
          and out[f"data{n}"]["mesh"]["data"] == n
          and out["fsdp2"]["mesh"]["fsdp"] == 2
          and out["tp2"]["mesh"]["tensor"] == 2,
          f"meshes: { {k: v['mesh'] for k, v in out.items()} }")
    check(out[f"data{n}"]["all_reduce"] > 0,
          f"no all-reduce in the compiled data{n} step")
    check(out["fsdp2"]["opt_state_sharded"],
          "fsdp2 left the optimizer state replicated")
    check(out["tp2"]["attention_kernel_sharded"],
          "tp2 left the attention weights replicated")
    return out


# -- do forked data workers leave the chip's client alone? -------------

def fork_phase(timeout_s=120):
    """``--worker-impl process`` forks its pool after the parent owns the
    chip (``data/iterators.py``, one pool per epoch stream).  Touch the
    device, read an epoch through forked workers, touch the device
    again: the workers only index and collate numpy, so the client must
    neither be touched nor broken.  A deadlocked child fails the phase
    through an alarm instead of hanging the run."""
    import signal

    import jax.numpy as jnp
    import numpy as np

    from unicore_tpu.data import UnicoreDataset, data_utils, iterators

    class Rows(UnicoreDataset):
        def __getitem__(self, i):
            return np.arange(i, i + 8)

        def __len__(self):
            return 64

        def collater(self, samples):
            return np.stack(samples)

    def on_alarm(signum, frame):
        raise SmokeFailure(f"forked data workers hung for {timeout_s}s")

    before = float(jnp.sum(jnp.ones((256, 256)) @ jnp.ones((256, 256))))
    base = Rows()
    sampler = data_utils.batch_by_size(np.arange(64), batch_size=8)

    def epoch():
        it = iterators.EpochBatchIterator(
            dataset=base, collate_fn=base.collater, batch_sampler=sampler,
            seed=1, num_workers=2,
        )
        return [np.asarray(b).tolist()
                for b in it.next_epoch_itr(shuffle=True)]

    threads = epoch()
    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(timeout_s)
    iterators.set_worker_impl("process")
    try:
        forked = epoch()
    finally:
        iterators.set_worker_impl("thread")
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
    after = float(jnp.sum(jnp.ones((256, 256)) @ jnp.ones((256, 256))))
    check(forked == threads, "forked workers gave another stream")
    check(before == after == 256.0 ** 3,
          f"the device answered {before} before the fork, {after} after")
    return {"batches": len(forked), "same_stream_as_threads": True,
            "device_ok_after_fork": True}


# -- is block_until_ready honest? --------------------------------------

def barrier_phase(n=8192, reps=8):
    """Time a chain of large matmuls to its end two ways:
    ``jax.block_until_ready`` and a fetch of real bytes.  An honest
    barrier cannot return before the arithmetic can be done."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def chain(x):
        for _ in range(reps):
            x = (x @ x) * (1.0 / n)
        return x

    x = jnp.ones((n, n), jnp.bfloat16)
    jax.block_until_ready(chain(x))  # compile
    times = {"block_until_ready": [], "fetch_bytes": []}
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(x))
        times["block_until_ready"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        np.asarray(chain(x)[:1, :1])
        times["fetch_bytes"].append(time.perf_counter() - t0)
    return {
        "matmul": f"{reps} x bf16 {n}^3",
        "flop": 2.0 * n ** 3 * reps,
        "block_until_ready_ms": [round(t * 1e3, 2)
                                 for t in times["block_until_ready"]],
        "fetch_bytes_ms": [round(t * 1e3, 2)
                           for t in times["fetch_bytes"]],
    }


# -- the ragged kernel, row pattern by row pattern ----------------------

def ragged_row_lengths(pattern, rows, blk, parity):
    """Context length of each of ``rows`` kernel rows (0: an empty row)
    for one named pattern; ``blk`` is a block's slots.  ``parity`` 1
    gives the first live row one block more, so every later block lands
    in the other K/V slot.  ``zeros_200`` takes the 204 rows it needs."""
    part = blk // 2 + 1
    if pattern == "zeros_200":
        rows = max(rows, 204)
    live = {
        "zeros_first": {rows - 3: 3 * blk, rows - 2: blk + 1,
                        rows - 1: 2 * blk},
        "zeros_last": {0: 3 * blk, 1: blk + 1, 2: 2 * blk},
        "zeros_between": {0: 2 * blk, 2: blk, 5: 3 * blk - 1, 7: 1,
                          rows // 2: 2 * blk + 1, rows - 1: part},
        "zeros_200": {0: blk + 3, 201: 2 * blk, 202: 5, 203: 3 * blk},
        "one_block": {r: n for r, n in enumerate(
            (blk, 1, blk - 1, blk, part, blk, 2, blk))},
        "odd_even": {rows - 7 + r: n * blk for r, n in enumerate(
            (1, 2, 3, 4, 5, 2, 1))},
        "partial_last": {r: n for r, n in enumerate(
            (blk + 1, 2 * blk - 1, 3 * blk + part, part, 4 * blk + 1,
             blk + part))},
    }[pattern]
    lengths = [0] * rows
    for r, n in live.items():
        lengths[r] = n
    first = min(live)
    lengths[first] += parity * blk
    return lengths


def _bf16_whole(x):
    """float32 numbers that bfloat16 holds exactly (low 16 bits cut)."""
    import numpy as np

    return (np.ascontiguousarray(x, np.float32).view(np.uint32)
            & np.uint32(0xFFFF0000)).view(np.float32)


def _pool_and_table(rng, lengths, tables_of, page_size, table_pages,
                    num_pages, lanes):
    """A pool of ``num_pages`` that reads POISON wherever no row may read:
    every table gets pages of its own (page 0 is the trash page the tables
    are padded with), filled up to its longest row.  ``tables_of[r]``
    names the table row ``r`` walks (rows of one prompt share theirs)."""
    import numpy as np

    reach = {}
    for r, n in enumerate(lengths):
        reach[tables_of[r]] = max(reach.get(tables_of[r], 0), n)
    check(sum(-(-n // page_size) for n in reach.values()) < num_pages,
          f"the rows' pages do not fit a pool of {num_pages}")
    pool = np.full((num_pages * page_size, lanes), POISON, np.float32)
    pages = rng.permutation(np.arange(1, num_pages))
    tables, at = {}, 0
    for name, n in reach.items():
        mine = pages[at:at + -(-n // page_size)]
        at += len(mine)
        row = np.zeros(table_pages, np.int32)
        row[:len(mine)] = mine
        tables[name] = row
        slots = (mine[:, None] * page_size
                 + np.arange(page_size)[None]).reshape(-1)[:n]
        pool[slots] = _bf16_whole(rng.randn(n, lanes))
    table = np.stack([tables[tables_of[r]] for r in range(len(lengths))])
    return pool, table


def ragged_row_case(pattern, size, pages_per_block, parity, width, seed):
    """One per-head case: the operands of ``ragged_paged_attention`` as
    numpy arrays.  A live row's queries sit at the end of its context: a
    chunk of up to ``width`` tokens on odd rows, one decode token on even
    ones."""
    import numpy as np

    rng = np.random.RandomState(seed)
    ps = size["page_size"]
    lengths = ragged_row_lengths(
        pattern, size["rows"], pages_per_block * ps, parity)
    positions = np.full((len(lengths), width), -1, np.int32)
    for r, n in enumerate(lengths):
        new = min(n, width if r % 2 else 1)
        positions[r, :new] = n - new + np.arange(new)
    return _per_head_case(rng, lengths, positions, size, pages_per_block)


def _per_head_case(rng, lengths, positions, size, pages_per_block, **more):
    """The operands of a per-head case from its rows' lengths and query
    positions: a pool and tables of their own, queries of whole bfloat16
    numbers."""
    import numpy as np

    ps, heads, d = size["page_size"], size["heads"], size["head_dim"]
    k, table = _pool_and_table(rng, lengths, list(range(len(lengths))), ps,
                               size["table_pages"], size["num_pages"],
                               heads * d)
    # values: the keys' numbers a lane further on (a POISON slot is POISON
    # in every lane, so it stays one)
    v = np.roll(k, 1, axis=1)
    q = _bf16_whole(rng.randn(*positions.shape, heads, d))
    return dict(q=q, k_pages=k, v_pages=v, page_table=table, positions=positions,
                lengths=np.asarray(lengths, np.int32), page_size=ps,
                scale=d ** -0.5, pages_per_block=pages_per_block,
                three_pass=False, **more)


def window_row_case(size, pages_per_block, parity, width, seed):
    """The row patterns a sliding layer brings, one call: a window that
    starts in the middle of a page (row 0), an empty row after a windowed
    one (1), a window that starts on a page's edge in the middle of a
    block or, at one page a block, on a block's (2), one that starts in
    block 0 (3), a row shorter than the window (4), a chunk whose first
    query sees a block its last one does not (6), a windowed last row.
    ``window`` is a block and three slots; ``blocks`` counts the blocks
    the rows walk (those from each row's first visible one)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    ps = size["page_size"]
    blk = pages_per_block * ps
    window = blk + 3
    live = {0: 2 * blk + ps // 2 + 1 + window, 2: blk + ps + window,
            3: window + 2, 4: max(1, window - 5), 6: 4 * blk + 1,
            size["rows"] - 1: 2 * blk}
    lengths = [0] * size["rows"]
    for r, n in live.items():
        lengths[r] = n
    lengths[0] += parity * blk
    positions = np.full((len(lengths), width), -1, np.int32)
    blocks = 0
    for r, n in enumerate(lengths):
        new = min(n, width if r % 2 == 0 and r else 1)
        positions[r, :new] = n - new + np.arange(new)
        if n:
            blocks += -(-n // blk) - min(
                max(n - new - window + 1, 0) // blk, -(-n // blk) - 1)
    return _per_head_case(rng, lengths, positions, size, pages_per_block,
                          window=window, blocks=blocks)


def latent_row_case(form, size, pages_per_block, parity, seed):
    """The latent step's shape, cut as ``serve/attention.py``
    ``write_latent_and_attend`` cuts it: one K/V head of ``latent_lanes``,
    ONE pool as keys and as values, three passes, a token's heads as query
    cells.  ``"decode"``: one token a row, live and empty rows mixed.
    ``"mixed"``: a rectangle of ``latent_chunk`` columns cut into tiles of
    ``latent_tile`` tokens, so most kernel rows are empty, empty ones lie
    between live ones, and the tiles of one prompt share a page table with
    lengths that grow by a tile."""
    import numpy as np

    rng = np.random.RandomState(seed)
    ps, lanes, heads = (size["page_size"], size["latent_lanes"],
                        size["latent_heads"])
    rows, chunk, blk = (size["latent_rows"], size["latent_chunk"],
                        pages_per_block * ps)
    # (context, new tokens) of each rectangle row; the first live row's
    # context grows by a block under ``parity``
    if form == "decode":
        chunk, tile = 1, 1
        seqs = [(blk + 3, 1), (0, 0), (3 * blk, 1), (5, 1), (0, 0),
                (2 * blk - 1, 1)] + [(0, 0)] * (rows - 7) + [(blk, 1)]
    else:
        tile = size["latent_tile"]
        seqs = [(0, 0), (2 * blk + 1, 1), (blk - 2, chunk), (0, 0),
                (7, 1), (3 * blk, chunk // 2 - 3), (0, 0), (0, 0),
                (blk, 1), (0, tile + 1)] + [(0, 0)] * (rows - 11) + [
                    (blk + 5, 2 * tile)]
    first = next(i for i, (_, new) in enumerate(seqs) if new)
    seqs[first] = (seqs[first][0] + parity * blk, seqs[first][1])
    row_positions = np.full((rows, chunk), -1, np.int32)
    for r, (context, new) in enumerate(seqs):
        row_positions[r, :new] = context + np.arange(new)
    tiles = chunk // tile
    tile_positions = row_positions.reshape(rows * tiles, tile)
    lengths = tile_positions.max(axis=1) + 1
    pool, table = _pool_and_table(
        rng, lengths, np.repeat(np.arange(rows), tiles), ps,
        size["latent_table_pages"], size["latent_num_pages"], lanes)
    q = _bf16_whole(rng.randn(rows * tiles, tile * heads, 1, lanes)) / 8
    return dict(q=q, k_pages=pool, v_pages=pool, page_table=table,
                positions=np.repeat(tile_positions, heads, axis=1),
                lengths=lengths, page_size=ps,
                scale=192 ** -0.5, pages_per_block=pages_per_block,
                three_pass=True)


def ragged_case_gap(case):
    """The kernel's largest distance from ``paged_attention_reference``
    (at ``highest`` precision) over the live query cells of ``case``, and
    whether every cell of every row, empty ones too, came out finite.
    The reference gathers the live rows alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from unicore_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention,
    )
    from unicore_tpu.serve.attention import paged_attention_reference

    case = dict(case)
    q, k_np, v_np, table, positions, lengths = (case.pop(n) for n in (
        "q", "k_pages", "v_pages", "page_table", "positions", "lengths"))
    case.pop("blocks", None)
    window = case.get("window", 0)
    k = jnp.asarray(k_np)
    v = k if v_np is k_np else jnp.asarray(v_np)
    out = np.asarray(ragged_paged_attention(
        jnp.asarray(q), k, v, jnp.asarray(table), jnp.asarray(positions),
        jnp.asarray(lengths), **case))
    live = np.flatnonzero(lengths)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(paged_attention_reference(
            jnp.asarray(q[live]), k, v, jnp.asarray(table[live]),
            jnp.asarray(positions[live]), jnp.asarray(lengths[live]),
            case["page_size"], case["scale"], window=window))
    cells = positions[live] >= 0
    return (float(np.abs(out[live] - ref)[cells].max()),
            bool(np.isfinite(out).all()))


def kernel_phase(seed, size):
    """Every row pattern through the COMPILED ragged kernel against the
    reference: the one place a DMA that is read before it has landed, or
    waited for by nobody, can show (interpret mode runs a copy where it
    is started)."""
    gaps = {}
    for pp in size["pages_per_block"]:
        for parity in (0, 1):
            cases = {
                f"{pattern}-w{width}": ragged_row_case(
                    pattern, size, pp, parity, width, seed)
                for pattern in size["patterns"]
                for width in size["widths"]}
            cases.update({
                f"latent_{form}": latent_row_case(
                    form, size, pp, parity, seed)
                for form in ("decode", "mixed")})
            cases.update({
                f"window-w{width}": window_row_case(
                    size, pp, parity, width, seed)
                for width in size["widths"]})
            for name, case in cases.items():
                gap, finite = ragged_case_gap(case)
                limit = size["latent_gap_limit" if case["three_pass"]
                             else "gap_limit"]
                check(finite and gap <= limit,
                      f"ragged kernel, {name}, {pp} pages a block, parity "
                      f"{parity}: {gap} from the reference (limit {limit}"
                      f"), finite {finite}")
                gaps[f"{name}-pp{pp}-parity{parity}"] = gap
    return {"cases": len(gaps), "gap_max": max(gaps.values()),
            "latent_gap_max": max(
                g for n, g in gaps.items() if n.startswith("latent")),
            "worst": max(gaps, key=gaps.get)}


# -- main --------------------------------------------------------------

def _chip_checks_step(rep, what, want_kernel=True):
    check(rep["platform"] == ["tpu"],
          f"{what}: the step ran on {rep['platform']}, not on a tpu")
    check(rep["tpu_custom_call"] > 0 or not want_kernel,
          f"{what}: no tpu_custom_call in the compiled train step — no "
          "Pallas kernel is in the program")
    check(rep["memory_analysis_gb"], f"{what}: empty memory analysis")
    check(all(rep["peak_bytes_in_use"]),
          f"{what}: memory_stats() came back empty: "
          f"{rep['peak_bytes_in_use']}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: no tpu: jax found {dev.platform!r} "
            f"({dev.device_kind}); nothing was run\n")
        return 1
    if len(devices) != args.chips:
        sys.stderr.write(
            f"chip_smoke: --chips {args.chips} but jax sees "
            f"{len(devices)} device(s); nothing was run\n")
        return 1

    from unicore_tpu.utils import configure_compile_cache

    cache_dir = configure_compile_cache()
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    if os.path.exists(REPORT):
        os.remove(REPORT)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    emit("start", device=device, jax=jax.__version__, chips=args.chips,
         compile_cache=cache_dir)
    try:
        if args.chips == 4:
            rep = mesh_phase(WORK, args.seed, BERT, devices)
            emit("mesh", **rep)
            for name, r in rep.items():
                # under tp the attention heads are sharded and take the
                # einsum path: pallas_call has no partitioning rule
                _chip_checks_step(r, name, want_kernel=name != "tp2")
                held = r["bytes_in_use"][:1 if name == "one" else 4]
                check(all(held), f"{name}: a device holds no buffers: "
                      f"{r['bytes_in_use']}")
        else:
            from bench import PEAK_BF16_FLOPS  # the one table of peaks

            rep = barrier_phase()
            floor_ms = rep["flop"] / PEAK_BF16_FLOPS[dev.device_kind] * 1e3
            rep["arithmetic_floor_ms"] = round(floor_ms, 2)
            rep["honest"] = min(rep["block_until_ready_ms"]) >= floor_ms
            emit("barrier", **rep)
            check(rep["honest"],
                  "block_until_ready returned before the matmuls could "
                  f"have run: {rep}")

            emit("fork", **fork_phase())

            rep = train_phase(WORK, args.seed, BERT)
            emit("train", **rep)
            _chip_checks_step(rep, "bert")
            check(rep["cache_entries"] > 0,
                  f"nothing was written to {rep['cache_dir']}")
            # a hit is the cache's own event; that it was the train step
            # (the one long compile) shows in the seconds
            check(rep["second_run_cache_hits"] >= 1
                  and max(rep["second_run_compile_s"])
                  < max(5.0, 0.5 * rep["first_compile_s"]),
                  "the second run's train step was not served from the "
                  "persistent cache: "
                  f"{rep['second_run_cache_hits']} hits, compiles of "
                  f"{rep['second_run_compile_s']} s after a first of "
                  f"{rep['first_compile_s']} s")
            flash = rep["kernel_dispatch"].get("flash_attention", {})
            check(flash and set(flash.values()) == {"pallas"},
                  f"flash attention dispatch: {flash}")

            emit("kernel", **kernel_phase(args.seed, KERNEL))

            rep = serve_phase(WORK, args.seed, LM)
            emit("serve", **rep)
            check(set(rep["attention_paths"].values()) == {"pallas"},
                  "a serve width did not take the ragged kernel: "
                  f"{rep['attention_paths']}")
    except SmokeFailure as e:
        emit("failed", error=str(e))
        sys.stderr.write(f"chip_smoke: FAILED: {e}\n")
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
