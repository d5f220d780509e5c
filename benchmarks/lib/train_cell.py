"""A train cell: ``unicore-train`` in this process, its step timed.

The entry point a user calls (``unicore_tpu_cli.train.cli_main``) builds
its Trainer as always; a subclass made here watches it from the outside
(the pattern of ``chip_smoke.py``): it installs the seeded weights, counts
the tokens of every staged batch, opens and closes the window between two
``block_until_ready`` barriers, and takes the numbers the comparison with
the plain reference needs from the first three updates of the SAME
compiled step and state that the window then drives.
"""

import collections
import contextlib
import gc
import math
import os
import shutil
import sys
import time

import numpy as np

from . import device, hostwatch, tracing, traffic, weights
from .device import log


PROGRAM_SEED = 1  # unicore-train's own --seed: the same for every run


class Probe:
    """What the harness sees of the trainer, and when."""

    CHECK_STEPS = 3

    def __init__(self, cell, seed, seconds, trace_dir):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace_dir = trace_dir
        self.warmup = self.CHECK_STEPS + 1  # updates before the window
        self.pad = None
        self.staged_tokens = collections.deque()
        self.first_batches = []
        self.calls = 0
        self.losses = []            # every processed update, in order
        self.grad_norms = []
        self.abstract = None
        self.first_grad_norms = None
        self.param_change_norms = None
        self.t0 = self.t1 = None
        self.window_updates = 0
        self.window_tokens = 0
        self.updates_at_open = None
        self.compiles_at_open = None
        self.compiles_in_window = None
        self.step_clock = []
        self.closed = False
        self.setup_s = None
        self.process_t0 = None
        self.memory_peak = 0
        self.watch = hostwatch.Watch()

    def mark(self, what):
        log(f"{time.perf_counter() - self.process_t0:8.2f} s  {what}")

    # -- set-up --------------------------------------------------------

    def install_weights(self, trainer):
        import jax

        params = trainer.state["params"]
        self.abstract = weights.abstract_of(params)
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, params)
        fresh = weights.make(self.abstract, self.seed, shardings)
        trainer.state = {**trainer.state, "params": fresh}
        self.mark("state built, seeded weights installed")
        self.pad = trainer.task.dictionary.pad()

    def on_stage(self, samples):
        n = 0
        for s in samples:
            toks = np.asarray(s["net_input"]["src_tokens"])
            n += int((toks != self.pad).sum())
        self.staged_tokens.append(n)
        if len(self.first_batches) < self.CHECK_STEPS:
            if len(samples) != 1:
                raise RuntimeError("the comparison follows one micro-batch "
                                   "per update (--update-freq 1)")
            s = samples[0]
            self.first_batches.append(
                (np.array(s["net_input"]["src_tokens"]),
                 np.array(s["target"])))

    # -- every update --------------------------------------------------

    def before_step(self, trainer):
        import jax

        if self.calls == self.warmup and self.t0 is None:
            trainer.flush_stats()
            jax.block_until_ready(trainer.state)
            self.updates_at_open = trainer.get_num_updates()
            self.compiles_at_open = device.Compiles.listen().count()
            if self.trace_dir:
                tracing.start(self.trace_dir)
            self.setup_s = time.perf_counter() - self.process_t0
            self.mark("window opens")
            self.watch.start()
            self.t0 = time.perf_counter()
        self.step_clock.append(time.perf_counter())

    def after_step(self, trainer):
        import jax

        call = self.calls
        self.calls += 1
        tokens = self.staged_tokens.popleft()
        if call == 0:
            self.mark("first update dispatched")
            # the first gradient as the optimizer got it: Adam's first
            # moment after one update is (1 - beta1) * g
            self.first_grad_norms = _leaf_norms(
                trainer.state["opt_state"]["exp_avg"],
                1.0 / (1.0 - self.cell["config"]["optim"]["beta1"]))
        if call == self.CHECK_STEPS - 1:
            self.param_change_norms = _change_norms(
                trainer.state["params"], self.abstract, self.seed)
        if self.t0 is None or self.closed:
            return
        self.window_updates += 1
        self.window_tokens += tokens
        if time.perf_counter() - self.t0 >= self.seconds:
            trainer.flush_stats()
            jax.block_until_ready(trainer.state)
            self.t1 = time.perf_counter()
            self.watch.stop()
            if self.trace_dir:
                tracing.stop()
            self.closed = True
            self.compiles_in_window = (
                device.Compiles.listen().count() - self.compiles_at_open)
            self.updates_done = trainer.get_num_updates() - self.updates_at_open
            self.memory_peak = device.memory_peak_bytes(
                list(trainer.mesh.devices.flat))
            # the loop's own stop condition ends the run at this boundary
            trainer.args.max_update = trainer.get_num_updates()

    def on_stats(self, logging_outputs, grad_norm):
        loss = sum(float(l.get("loss", 0)) for l in logging_outputs or ())
        n = sum(float(l.get("sample_size", 0)) for l in logging_outputs or ())
        self.losses.append(loss / n / math.log(2) if n else float("nan"))
        self.grad_norms.append(None if grad_norm is None else float(grad_norm))


def _leaf_norms(tree, scale):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) * scale
        for x in jax.tree_util.tree_leaves(t)])
    return fn(tree)


def _change_norms(params, abstract, seed):
    import jax
    import jax.numpy as jnp

    start = weights.make(abstract, seed,
                         jax.tree_util.tree_map(lambda x: x.sharding, params))
    fn = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])
    return fn(params, start)


@contextlib.contextmanager
def watched_trainer(probe):
    """``cli_main`` builds its Trainer as always; this is the class it
    finds under that name meanwhile."""
    import unicore_tpu_cli.train as train_cli
    from unicore_tpu.trainer import StagedBatch

    class Watched(train_cli.Trainer):
        def init_state(self, sample):
            fresh = self.state is None
            super().init_state(sample)
            if fresh:
                probe.install_weights(self)

        def stage_batches(self, samples):
            if not isinstance(samples, StagedBatch):
                if probe.pad is None:
                    probe.pad = self.task.dictionary.pad()
                probe.on_stage(samples)
            return super().stage_batches(samples)

        def train_step(self, samples):
            probe.before_step(self)
            out = super().train_step(samples)
            probe.after_step(self)
            return out

        def _reduce_and_log_stats(self, logging_outputs, sample_size,
                                  grad_norm=None):
            probe.on_stats(logging_outputs, grad_norm)
            return super()._reduce_and_log_stats(
                logging_outputs, sample_size, grad_norm)

    made = []
    orig_init = Watched.__init__

    def init(self, *a, **kw):
        orig_init(self, *a, **kw)
        made.append(self)

    Watched.__init__ = init
    orig = train_cli.Trainer
    train_cli.Trainer = Watched
    try:
        yield made
    finally:
        train_cli.Trainer = orig


def _import_example(name):
    """``--user-dir examples/<name>`` imports the example under its bare
    name; keep it ONE module with ``examples.<name>`` or its registrations
    run twice."""
    import importlib

    mod = importlib.import_module(f"examples.{name}")
    sys.modules.setdefault(name, mod)


def argv_for(cell, seed, data_dir, save_dir, chips):
    cfg, tr = cell["config"], cell["traffic"]
    run, opt = cfg["run"], cfg["optim"]
    repo = cell["root_program"]
    return [
        data_dir, "--valid-subset", "valid",
        "--user-dir", os.path.join(repo, *run["user_dir"].split("/")),
        *run["cli"], *cell["family"].train_argv(cfg),
        "--optimizer", "adam",
        "--adam-betas", f"({opt['beta1']}, {opt['beta2']})",
        "--adam-eps", repr(opt["eps"]),
        "--weight-decay", repr(opt["weight_decay"]),
        "--clip-norm", repr(opt["clip_norm"]),
        "--lr-scheduler", "fixed", "--lr", repr(opt["lr"]),
        "--batch-size", str(tr["batch_per_chip"] * chips),
        "--max-seq-len", str(tr["seq_len"]),
        "--required-batch-size-multiple", "1", "--update-freq", "1",
        # the data order and the masking draw from this one: fixed, so that
        # every --seed sees batches of the same sizes (weights and token
        # ids are what --seed changes)
        "--seed", str(PROGRAM_SEED),
        "--max-update", "100000000", "--log-interval", "50",
        "--log-format", "simple", "--no-progress-bar",
        "--no-save", "--disable-validation",
        "--save-dir", save_dir, "--tmp-save-dir", save_dir + "_tmp",
        *tr.get("cli", []),
    ]


def run(cell, seed, seconds, trace, devices, workdir, process_t0):
    """Run the cell once.  Returns the facts of the run; the comparison
    with the reference and the metrics are taken from them by the
    caller."""
    import jax

    from unicore_tpu.data import IndexedRecordWriter
    from unicore_tpu.distributed import utils as dist_utils
    from unicore_tpu.ops import backend
    import unicore_tpu_cli.train as train_cli

    cfg, tr = cell["config"], cell["traffic"]
    chips = len(devices)
    _import_example(cfg["run"]["user_dir"].split("/")[-1])
    dist_utils.reset_mesh()
    if seconds <= 0:
        raise ValueError("--seconds must be positive")
    window = min(seconds, tracing.TRACE_SECONDS) if trace else seconds
    trace_dir = os.path.join(workdir, "trace") if trace else None
    probe = Probe(cell, seed, window, trace_dir)
    probe.process_t0 = process_t0

    symbols = cfg["run"]["symbols"]
    batch = tr["batch_per_chip"] * chips
    updates = probe.warmup + math.ceil(
        tr["updates_per_second_budget"] * window) + 4
    data_dir = os.path.join(workdir, "data")
    t = time.perf_counter()
    corpus_tokens = traffic.write_corpus(
        data_dir, tr, seed, batch * updates, symbols, IndexedRecordWriter)
    log(f"corpus: {batch * updates} records, {corpus_tokens} tokens, "
        f"{time.perf_counter() - t:.2f} s")

    probe.mark("corpus written; entering unicore-train")
    argv = argv_for(cell, seed, data_dir, os.path.join(workdir, "ckpt"), chips)
    saved = sys.argv
    sys.argv = ["unicore-train"] + argv
    device.Compiles.listen()
    try:
        with watched_trainer(probe) as made:
            train_cli.cli_main()
    finally:
        sys.argv = saved
    if len(made) != 1:
        raise RuntimeError(f"cli_main built {len(made)} trainers")
    trainer = made[0]
    if not probe.closed:
        raise RuntimeError(
            "the run ended before the window closed: the corpus held "
            f"{updates} updates, the window took {probe.window_updates}; "
            "raise updates_per_second_budget in the traffic file")
    facts = {
        "probe": probe,
        "window_s": probe.t1 - probe.t0,
        "mesh": dict(zip(trainer.mesh.axis_names, trainer.mesh.devices.shape)),
        "dispatch": backend.dispatch_report(),
        "host_timers": dict(trainer.host_timers),
        "first_grad_norms": [float(x) for x in probe.first_grad_norms],
        "param_change_norms": [float(x) for x in probe.param_change_norms],
    }
    # free the program's state before the reference takes the device
    trainer.state = None
    trainer._jit_train_step = None
    trainer._compiled_train_step = None
    del trainer, made
    gc.collect()
    jax.clear_caches()
    shutil.rmtree(data_dir, ignore_errors=True)
    return facts
