"""``unicore-train``: train a model on one or more TPU hosts.

Behavioral parity target: ``unicore_cli/train.py`` — epoch loop with
curriculum shuffle gating, grad-accum grouping, periodic validation +
checkpointing, patience-based early stop, and the
max-update/min-lr/wall-clock stop conditions.  Differences by design: no
per-GPU process spawning (jax runs one process per host, SPMD inside) and
``--profile`` wraps the run in ``jax.profiler.trace`` instead of nvprof.

Independent implementation: the loop is a :class:`TrainLoop` object —
stop conditions, patience state, and the checkpoint manager live on the
instance instead of function attributes and six-argument call chains.
"""

import argparse
import logging
import math
import os
import sys
import time
from typing import Optional

import numpy as np

import jax

from unicore_tpu import options, tasks, utils
from unicore_tpu.checkpoint_utils import CheckpointManager
from unicore_tpu.data import iterators
from unicore_tpu.distributed import utils as distributed_utils
from unicore_tpu.logging import metrics, progress_bar
from unicore_tpu.trainer import Trainer

logging.basicConfig(
    format="%(asctime)s | %(levelname)s | %(name)s | %(message)s",
    datefmt="%Y-%m-%d %H:%M:%S",
    level=os.environ.get("LOGLEVEL", "INFO").upper(),
    stream=sys.stdout,
)
logger = logging.getLogger("unicore_tpu_cli.train")


def _annotate_iter(iterable, name):
    """Wrap each ``next()`` in a profiler TraceAnnotation so data-wait time
    shows as a named range in captured traces (the reference's
    ``record_function`` phase structure, unicore_cli/train.py:213-215)."""
    it = iter(iterable)
    while True:
        with jax.profiler.TraceAnnotation(name):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


class TrainLoop:
    """Drives epochs: train, validate, checkpoint, decide when to stop."""

    def __init__(self, args, trainer, task, ckpt: CheckpointManager,
                 shutdown=None):
        self.args = args
        self.trainer = trainer
        self.task = task
        self.ckpt = ckpt
        self.shutdown = shutdown  # resilience.GracefulShutdown (or None)
        self.valid_subsets = args.valid_subset.split(",")
        # patience tracking (reference should_stop_early, train.py:147-172)
        self._runs_without_improvement = 0
        self._patience_best = None
        # data-guard counter watermarks for the delta-based
        # data_skipped/data_retries/data_corrupt_rate metrics; None
        # until the first boundary snapshots a baseline — a resumed
        # run's restored skip-log history must not read as fresh skips
        self._data_seen = None
        # pipelined dispatch (--pipeline-depth >= 2): boundary checks
        # (writer poll, data health) ride the DRAIN point — this
        # watermark tells a boundary whether the trainer retired any
        # step since the last one
        self._retired_seen = 0

    # -- stop conditions ----------------------------------------------

    def _hit_hard_limits(self):
        """max-update / wall-clock limits, checked after every step."""
        updates = self.trainer.get_num_updates()
        max_update = self.args.max_update or math.inf
        # lagged-stats pipeline: only pay a flush when the optimistic
        # (dispatched) count could hit the limit, then re-check exactly
        if updates + self.trainer.num_pending_updates() >= max_update:
            self.trainer.flush_stats()
            updates = self.trainer.get_num_updates()
        if updates >= max_update:
            logger.info(
                "stopping: num_updates %d >= --max-update %s",
                updates, max_update,
            )
            return True
        if self.args.stop_time_hours > 0:
            hours = self.trainer.cumulative_training_time() / 3600.0
            if hours > self.args.stop_time_hours:
                logger.info(
                    "stopping: %.2f training hours > --stop-time-hours %s",
                    hours, self.args.stop_time_hours,
                )
                self.trainer.flush_stats()  # stop -> save/validate follow
                return True
        return False

    def _patience_exhausted(self, valid_loss):
        if valid_loss is None or self.args.patience <= 0:
            return False
        better = (
            self._patience_best is None
            or (valid_loss > self._patience_best
                if self.args.maximize_best_checkpoint_metric
                else valid_loss < self._patience_best)
        )
        if better:
            self._patience_best = valid_loss
            self._runs_without_improvement = 0
            return False
        self._runs_without_improvement += 1
        if self._runs_without_improvement >= self.args.patience:
            logger.info(
                "early stop: no validation improvement in the last %d runs",
                self.args.patience,
            )
            return True
        return False

    # -- epoch loop ----------------------------------------------------

    def run(self, epoch_itr):
        """Epoch loop until a stop condition fires."""
        max_epoch = self.args.max_epoch or math.inf
        lr = self.trainer.get_lr()
        while epoch_itr.next_epoch_idx <= max_epoch:
            if lr <= self.args.stop_min_lr:
                logger.info(
                    "stopping: lr %g <= --stop-min-lr %g",
                    lr, self.args.stop_min_lr,
                )
                break
            valid_losses, stop = self.train_epoch(epoch_itr)
            if stop:
                break
            lr = self.trainer.lr_step(epoch_itr.epoch, valid_losses[0])
            epoch_itr = self.trainer.get_train_iterator(
                epoch_itr.next_epoch_idx,
                load_dataset=self.task.has_sharded_data("train"),
                disable_iterator_cache=False,
            )

    @metrics.aggregate("train")
    def train_epoch(self, epoch_itr):
        """One epoch of updates; returns (valid_losses, should_stop)."""
        args = self.args
        itr = epoch_itr.next_epoch_itr(
            shuffle=(epoch_itr.next_epoch_idx > args.curriculum),
        )
        freq_schedule = args.update_freq
        update_freq = (
            freq_schedule[epoch_itr.epoch - 1]
            if epoch_itr.epoch <= len(freq_schedule)
            else freq_schedule[-1]
        )
        itr = iterators.GroupedIterator(itr, update_freq)
        progress = self._progress(itr, epoch_itr.epoch)

        # the watchdog's timeout dump names this epoch's pipeline state
        # (worker impl + stuck dataset indices) next to the writer's
        self.trainer.attach_input_pipeline(getattr(epoch_itr, "status", None))
        # baseline the data-guard watermark BEFORE the first pull: a
        # restored skip log's history must not count as fresh skips,
        # while a skip in the very first batch still must
        self._log_data_health(epoch_itr)
        self.trainer.begin_epoch(epoch_itr.epoch)
        valid_losses, stop = [None], False
        num_updates = self.trainer.get_num_updates()
        # a resumed run can ALREADY sit at a stop limit — e.g. the
        # previous process was signalled while its FINAL save streamed
        # on the background writer, so its checkpoint carries
        # max-update state.  The in-loop check runs only AFTER a
        # dispatch; without this pre-check such a resume trains one
        # update past the limit (caught by the chaos harness's
        # kill-during-background-write legs: 11 updates vs the
        # oracle's --max-update 10)
        if self._hit_hard_limits():
            return valid_losses, True
        logger.info("Start iterating over samples")
        stream = _annotate_iter(progress, "train/data-wait")
        staged = self._next_staged(stream)
        while staged is not None:
            with metrics.aggregate("train_inner"):
                log_output = self.trainer.train_step(staged)

            if log_output is not None:
                num_updates = self.trainer.get_num_updates()
                if num_updates % args.log_interval == 0:
                    stats = _with_wall(
                        metrics.get_smoothed_values("train_inner")
                    )
                    progress.log(stats, tag="train_inner", step=num_updates)
                    metrics.reset_meters("train_inner")

            valid_losses, stop = self.validate_and_save(
                epoch_itr, end_of_epoch=not itr.has_next()
            )
            if stop:
                break
            # input double-buffering: pull + stack + device-put group N+1
            # while the device still executes step N.  Deliberately AFTER
            # the boundary above, so a preemption checkpoint's iterator
            # position never counts a group that was staged but not
            # dispatched (the chaos harness's bit-exact resume contract).
            staged = self._next_staged(stream)

        logger.info("end of epoch %d (average epoch stats below)",
                    epoch_itr.epoch)
        progress.print(
            _with_wall(metrics.get_smoothed_values("train")),
            tag="train", step=num_updates,
        )
        metrics.reset_meters("train")
        return valid_losses, stop

    def _next_staged(self, stream):
        """Pull the next micro-batch group and stage it onto the device
        (overlaps the currently-executing step); None at epoch end.

        The pull is armed on the step watchdog (a wedged worker or
        prefetch pump is a hang like any other; the dump names the
        pipeline state) and timed into ``host_timers`` — the
        steady-state wait here is bench's ``input_stall_ms``, the
        data-pipeline stall isolated from device step time."""
        t0 = time.perf_counter()
        with self.trainer.input_wait():
            samples = next(stream, None)
        ht = self.trainer.host_timers
        ht["input_wait_s"] += time.perf_counter() - t0
        ht["input_waits"] += 1
        if samples is None:
            return None
        with jax.profiler.TraceAnnotation("train/stage"):
            return self.trainer.stage_batches(samples)

    def _log_data_health(self, epoch_itr):
        """Data-guard metrics, polled on the MAIN thread each boundary
        (worker threads/processes must not touch the metrics
        aggregators): deltas of the skip/retry counters plus the
        corrupt-rate gauge the budget ladder watches."""
        counters_fn = getattr(epoch_itr.dataset, "data_counters", None)
        if counters_fn is None:
            return
        c = counters_fn()
        if c is None:
            return
        if self._data_seen is None:  # first boundary: baseline only
            self._data_seen = {k: c[k] for k in ("skipped", "retries")}
            return
        d_skip = c["skipped"] - self._data_seen["skipped"]
        d_retry = c["retries"] - self._data_seen["retries"]
        if d_skip > 0:
            metrics.log_scalar("data_skipped", d_skip, priority=612, round=0)
        if d_retry > 0:
            metrics.log_scalar("data_retries", d_retry, priority=613, round=0)
        if d_skip > 0 or d_retry > 0:
            metrics.log_scalar(
                "data_corrupt_rate", c["corrupt_rate"], priority=614,
                round=5, weight=0,
            )
            self._data_seen = {k: c[k] for k in ("skipped", "retries")}

    def validate_and_save(self, epoch_itr, end_of_epoch):
        args = self.args
        # preemption (SIGTERM/SIGINT): flush the lagged pipeline so the
        # checkpoint carries exact counts, write it, and stop — the save
        # rides the normal do_save=stop path below; validation is skipped
        # because the grace window is for persisting state, not metrics
        preempted = self.shutdown is not None and self.shutdown.requested
        # lagged-stats pipeline: flush when this round could owe an action
        # (interval conditions are evaluated on the exact processed count;
        # checkpoints/validation need exact meters) — in the common
        # no-action step this stays flush-free so dispatch keeps pipelining
        opt_updates = (
            self.trainer.get_num_updates() + self.trainer.num_pending_updates()
        )
        may_act = end_of_epoch or (
            args.save_interval_updates > 0
            and opt_updates > 0
            and opt_updates % args.save_interval_updates == 0
        ) or (
            args.validate_interval_updates > 0
            and opt_updates > 0
            and opt_updates % args.validate_interval_updates == 0
        )
        retired = self.trainer.retired_steps
        drained = retired != self._retired_seen
        self._retired_seen = retired
        if (self.trainer.pipeline_depth <= 1 or drained or may_act
                or preempted):
            # a background checkpoint write that failed since the last
            # boundary surfaces HERE, on the main thread, before anything
            # else this round — the run must never keep training on the
            # belief that a save landed when it did not.  At
            # --pipeline-depth >= 2 these checks ride the DRAIN point:
            # while the in-flight ring fills (no step retired, no action
            # due) they would only serialize dispatch — steady state
            # drains every boundary, so the poll cadence is unchanged.
            self.ckpt.poll()
            self._log_data_health(epoch_itr)
        if preempted:
            logger.warning(
                "preemption: checkpointing and exiting at this step boundary"
            )
            self.trainer.flush_stats()
        if may_act:
            self.trainer.flush_stats()
            opt_updates = self.trainer.get_num_updates()
        updates = self.trainer.get_num_updates()
        stop = self._hit_hard_limits() or preempted

        # what this round owes: a checkpoint, a validation pass, both, or
        # neither (reference validate_and_save condition trees,
        # unicore_cli/train.py:247-320).  Interval conditions test the
        # OPTIMISTIC count: the processed count is stale by stats_lag, so
        # testing it would re-fire the condition on the step after each
        # boundary (duplicate checkpoint + validation)
        save_now = stop or (
            end_of_epoch
            and epoch_itr.epoch % args.save_interval == 0
            and not args.no_epoch_checkpoints
        ) or (
            args.save_interval_updates > 0
            and opt_updates > 0
            and opt_updates % args.save_interval_updates == 0
            and updates >= args.validate_after_updates
        )
        validate_now = not args.disable_validation and not preempted and (
            stop
            or (not end_of_epoch and save_now)
            or (
                end_of_epoch
                and epoch_itr.epoch % args.validate_interval == 0
                and not args.no_epoch_checkpoints
            )
            or (
                args.validate_interval_updates > 0
                and opt_updates > 0
                and opt_updates % args.validate_interval_updates == 0
            )
        )

        valid_losses = [None]
        if validate_now:
            with jax.profiler.TraceAnnotation("train/validate"):
                valid_losses = self.validate(epoch_itr)
        stop |= self._patience_exhausted(valid_losses[0])
        with jax.profiler.TraceAnnotation("train/checkpoint"):
            self.ckpt.save(
                self.trainer, epoch_itr, valid_losses[0],
                do_save=(save_now or stop),
            )
        return valid_losses, stop

    def validate(self, epoch_itr):
        """Run every validation subset; returns the checkpoint-metric values."""
        # drain lagged train stats BEFORE the new_root aggregator below —
        # flushing inside it would log train scalars into the valid meters
        self.trainer.flush_stats()
        self.task.begin_valid_epoch(epoch_itr.epoch, self.trainer.model)
        losses = []
        for subset in self.valid_subsets:
            logger.info('begin validation on "%s" subset', subset)
            itr = self.trainer.get_valid_iterator(subset).next_epoch_itr(
                shuffle=False
            )
            progress = self._progress(
                itr, epoch_itr.epoch, prefix=f"valid on '{subset}' subset"
            )
            with metrics.aggregate(new_root=True) as agg:
                logging_outputs = []
                for i, sample in enumerate(progress):
                    if (self.args.max_valid_steps is not None
                            and i > self.args.max_valid_steps):
                        break
                    _, _, sample_logs = self.trainer.valid_step(sample)
                    logging_outputs.extend(sample_logs)
                self.task.reduce_metrics(
                    logging_outputs, self.trainer.loss, subset
                )
            stats = self._valid_stats(agg.get_smoothed_values())
            progress.print(stats, tag=subset,
                           step=self.trainer.get_num_updates())
            if self.args.best_checkpoint_metric in stats:
                losses.append(stats[self.args.best_checkpoint_metric])
        return losses or [None]

    def _valid_stats(self, stats):
        stats["num_updates"] = self.trainer.get_num_updates()
        metric = self.args.best_checkpoint_metric
        if self.ckpt.best.value is not None and metric in stats:
            fold = max if self.args.maximize_best_checkpoint_metric else min
            stats[f"best_{metric}"] = fold(self.ckpt.best.value, stats[metric])
        return stats

    def _progress(self, itr, epoch, prefix=None):
        return progress_bar.progress_bar(
            itr,
            log_format=self.args.log_format,
            log_interval=self.args.log_interval,
            epoch=epoch,
            prefix=prefix,
            tensorboard_logdir=(
                self.args.tensorboard_logdir
                if getattr(self.args, "distributed_rank", 0) == 0
                else None
            ),
            default_log_format=(
                "tqdm" if not self.args.no_progress_bar else "simple"
            ),
        )


def _with_wall(stats):
    stats["wall"] = round(metrics.get_meter("default", "wall").elapsed_time, 0)
    return stats


def main(args) -> None:
    utils.import_user_module(args)
    iterators.set_worker_impl(getattr(args, "worker_impl", "thread"))
    if getattr(args, "batch_size_per_device", None):
        if args.batch_size is not None:
            raise ValueError(
                "--batch-size and --batch-size-per-device are exclusive"
            )
        args.batch_size = args.batch_size_per_device * jax.local_device_count()
        args.batch_size_valid = (
            getattr(args, "batch_size_valid", None) or args.batch_size
        )
        logger.info(
            "--batch-size-per-device %d x %d local devices -> "
            "--batch-size %d per host",
            args.batch_size_per_device, jax.local_device_count(),
            args.batch_size,
        )
    if args.batch_size is None:
        raise ValueError("--batch-size is required")
    if not args.loss:
        raise ValueError("--loss is required to train a model")
    metrics.reset()
    np.random.seed(args.seed)

    logger.info(args)
    task = tasks.setup_task(args)
    model = task.build_model(args)
    loss = task.build_loss(args)
    for subset in args.valid_subset.split(","):
        task.load_dataset(subset, combine=False, epoch=1)
    logger.info("task: %s", type(task).__name__)
    logger.info("model: %s", type(model).__name__)
    logger.info("loss: %s", type(loss).__name__)

    trainer = Trainer(args, task, model, loss)
    logger.info("training on %d devices", trainer.data_parallel_world_size)
    logger.info("batch size per host = %s", args.batch_size)

    is_master = getattr(args, "distributed_rank", 0) == 0
    ckpt = CheckpointManager(args, is_master)
    extra_state, epoch_itr = ckpt.restore(trainer, disable_iterator_cache=False)
    # the watchdog's timeout dump names the writer state (slow background
    # write != hung device step) and the rewind ladder serializes against
    # in-flight background saves
    trainer.attach_checkpoint_writer(ckpt.writer)

    shutdown = None
    if not getattr(args, "no_graceful_shutdown", False):
        from unicore_tpu.resilience import GracefulShutdown

        shutdown = GracefulShutdown().install()

    import time
    started = time.perf_counter()
    loop = TrainLoop(args, trainer, task, ckpt, shutdown=shutdown)
    try:
        loop.run(epoch_itr)
        # the exit-0 gate: every in-flight background save must LAND
        # before the run may report success — and a failed one raises
        # here (non-zero exit) instead of vanishing with the process.
        # A preemption exit passes through this same gate, so a
        # graceful SIGTERM's final checkpoint is provably on disk.
        ckpt.drain()
    finally:
        # order matters: the checkpoint worker drains BEFORE the process
        # exits (a preemption save must land on disk), then the trainer
        # releases its trajectory/watchdog resources
        ckpt.close()
        trainer.close()
        if hasattr(epoch_itr, "close"):
            epoch_itr.close()
        if shutdown is not None:
            shutdown.uninstall()
    if shutdown is not None and shutdown.requested:
        logger.warning(
            "exiting after preemption checkpoint (%s)",
            "SIGTERM" if shutdown.signum == 15 else str(shutdown.signum),
        )
    logger.info("done training in %.1f seconds", time.perf_counter() - started)


def cli_main(modify_parser: Optional[argparse.ArgumentParser] = None) -> None:
    parser = options.get_training_parser()
    args = options.parse_args_and_arch(parser, modify_parser=modify_parser)
    if getattr(args, "cpu", False):
        jax.config.update("jax_platforms", "cpu")
    utils.configure_compile_cache()
    if getattr(args, "profile", False):
        with jax.profiler.trace(
            os.path.join(args.save_dir, "jax_trace"),
            create_perfetto_link=False,
        ):
            distributed_utils.call_main(args, main)
    else:
        distributed_utils.call_main(args, main)


if __name__ == "__main__":
    cli_main()
