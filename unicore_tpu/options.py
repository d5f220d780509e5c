"""Command-line options (reference: unicore/options.py).

Same two-pass design: parse known args to discover ``--arch`` / ``--task`` /
registry choices, let each chosen class ``add_args()`` extend the parser,
then re-parse and apply the architecture preset.  Flag names match the
reference wherever the concept survives the TPU redesign, so downstream
launch scripts keep working; GPU-only knobs are accepted-and-ignored (noted
inline) and TPU-mesh knobs are new.
"""

import argparse

from unicore_tpu import utils
from unicore_tpu.registry import REGISTRIES, set_defaults


def get_training_parser(default_task="test"):
    parser = get_parser("Trainer", default_task)
    add_dataset_args(parser, train=True)
    add_distributed_training_args(parser)
    add_optimization_args(parser)
    add_checkpoint_args(parser)
    add_fault_tolerance_args(parser)
    add_model_args(parser)
    return parser


def get_validation_parser(default_task=None):
    parser = get_parser("Validation", default_task)
    add_dataset_args(parser, train=True)
    add_distributed_training_args(parser)
    add_checkpoint_args(parser)
    add_model_args(parser)
    group = parser.add_argument_group("Evaluation")
    add_common_eval_args(group)
    return parser


def parse_args_and_arch(
    parser,
    input_args=None,
    parse_known=False,
    suppress_defaults=False,
    modify_parser=None,
):
    """Two-pass parse: discover dynamic choices, extend the parser with the
    chosen classes' args, re-parse, then apply the arch preset.  Covers the
    reference CLI contract (options.py:36-148) so ``unicore-train``
    command lines work unchanged."""
    if suppress_defaults:
        # Variant used by checkpoint arg-merging: run the normal two-pass
        # parse once just to learn the full flag universe, then strip every
        # default to None and keep ONLY flags the user typed explicitly.
        args = parse_args_and_arch(
            parser,
            input_args=input_args,
            parse_known=parse_known,
            suppress_defaults=False,
        )
        suppressed_parser = argparse.ArgumentParser(
            add_help=False, parents=[parser], allow_abbrev=False
        )
        suppressed_parser.set_defaults(**{k: None for k, v in vars(args).items()})
        args = suppressed_parser.parse_args(input_args)
        return argparse.Namespace(
            **{k: v for k, v in vars(args).items() if v is not None}
        )

    from unicore_tpu.models import ARCH_CONFIG_REGISTRY, ARCH_MODEL_REGISTRY

    # --user-dir plugins must register their tasks/archs/losses before the
    # first real parse, or the dynamic-choice flags below would reject them
    _preload_user_module(input_args)

    if modify_parser is not None:
        modify_parser(parser)

    # pass 1: only the dynamic-choice flags (--arch/--task/--optimizer/...)
    # matter here; everything else is along for the ride
    args, _ = parser.parse_known_args(input_args)

    # grow the parser with the flags owned by each chosen class
    if hasattr(args, "arch"):
        model_specific_group = parser.add_argument_group(
            "Model-specific configuration",
            # SUPPRESS keeps untyped model flags out of the namespace so the
            # arch preset below can tell "user said" from "default"
            argument_default=argparse.SUPPRESS,
        )
        ARCH_MODEL_REGISTRY[args.arch].add_args(model_specific_group)

    for registry_name, registry_info in REGISTRIES.items():
        choice = getattr(args, registry_name, None)
        if choice is not None:
            cls = registry_info["registry"][choice]
            if hasattr(cls, "add_args"):
                cls.add_args(parser)

    if hasattr(args, "task"):
        from unicore_tpu.tasks import TASK_REGISTRY

        TASK_REGISTRY[args.task].add_args(parser)

    # the caller's hook runs again because add_args may have reset defaults
    if modify_parser is not None:
        modify_parser(parser)

    # pass 2: the full flag universe
    if parse_known:
        args, extra = parser.parse_known_args(input_args)
    else:
        args = parser.parse_args(input_args)
        extra = None

    if hasattr(args, "batch_size_valid") and args.batch_size_valid is None:
        args.batch_size_valid = args.batch_size
    args.bf16 = getattr(args, "bf16", False)
    args.fp16 = getattr(args, "fp16", False)

    # arch preset: fills every model flag the user did NOT type
    if hasattr(args, "arch"):
        ARCH_CONFIG_REGISTRY[args.arch](args)

    # registry choices whose add_args never ran (short-circuited parse)
    # still owe their defaults to the namespace
    for registry_name, registry_info in REGISTRIES.items():
        choice = getattr(args, registry_name, None)
        if choice is not None:
            cls = registry_info["registry"][choice]
            set_defaults(args, cls)

    if parse_known:
        return args, extra
    return args


def _preload_user_module(input_args=None):
    """Import the --user-dir plugin (if any) ahead of real parsing, using a
    throwaway parser that sees only that flag."""
    peek = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    peek.add_argument("--user-dir", default=None)
    peeked, _ = peek.parse_known_args(input_args)
    utils.import_user_module(peeked)


def get_parser(desc, default_task="test"):
    _preload_user_module()

    parser = argparse.ArgumentParser(allow_abbrev=False)
    # fmt: off
    parser.add_argument('--no-progress-bar', action='store_true', help='disable progress bar')
    parser.add_argument('--log-interval', type=int, default=100, metavar='N',
                        help='emit a stats line every N batches when the bar is off')
    parser.add_argument('--log-memory', type=int, default=0, metavar='N',
                        help='log a device HBM bytes-in-use gauge (mem_gb) '
                             'every N updates (0 = off); HBM stats are also '
                             'dumped automatically when a step fails')
    parser.add_argument('--log-format', default=None, help='log format to use',
                        choices=['json', 'none', 'simple', 'tqdm'])
    parser.add_argument('--tensorboard-logdir', metavar='DIR', default='',
                        help='tensorboard event-file directory (empty = disabled)')
    parser.add_argument('--wandb-project', metavar='WANDB', default='',
                        help='wandb project name (empty = disabled)')
    parser.add_argument('--seed', default=1, type=int, metavar='N',
                        help='RNG seed for params, dropout streams, and data order')
    parser.add_argument('--cpu', action='store_true', help='run on CPU instead of TPU')
    parser.add_argument('--fp16', action='store_true', help='use fp16 compute with dynamic loss scaling')
    parser.add_argument('--bf16', action='store_true', help='use bf16 compute (TPU-native; no loss scaling)')
    parser.add_argument('--bf16-sr', action='store_true',
                        help='stochastic rounding on the fp32-master -> bf16 param copy')
    parser.add_argument('--allreduce-fp32-grad', action='store_true',
                        help='reduce gradients in fp32 (grads are kept fp32 across the mesh)')
    parser.add_argument('--fp16-no-flatten-grads', action='store_true', help='(compat; grads are pytrees)')
    parser.add_argument('--fp16-init-scale', default=2 ** 7, type=int,
                        help='default loss-scale initial value')
    parser.add_argument('--fp16-scale-window', type=int,
                        help='number of clean updates before doubling the loss scale')
    parser.add_argument('--fp16-scale-tolerance', default=0.0, type=float,
                        help='tolerated fraction of overflows within the scale window')
    parser.add_argument('--min-loss-scale', default=1e-4, type=float, metavar='D',
                        help='minimum fp16 loss scale, after which training aborts')
    parser.add_argument('--threshold-loss-scale', type=float,
                        help='threshold fp16 loss scale from below')
    parser.add_argument('--user-dir', default=None,
                        help='path to a python module containing custom tasks/models/losses')
    parser.add_argument('--empty-cache-freq', default=0, type=int,
                        help='(compat; XLA manages device memory — accepted and ignored)')
    parser.add_argument('--all-gather-list-size', default=16384, type=int,
                        help='max bytes for pickled non-summable logging outputs gathered across hosts')
    parser.add_argument('--suppress-crashes', action='store_true',
                        help='suppress crashes when training with the entry point so that the '
                             'main method can return a value (useful for sweeps)')
    parser.add_argument('--profile', action='store_true',
                        help='capture a jax profiler trace for the run (xplane format)')
    parser.add_argument('--ema-decay', default=-1.0, type=float,
                        help='enable on-device EMA of params with this decay (<=0 disables)')
    parser.add_argument('--validate-with-ema', action='store_true',
                        help='run validation with the EMA params')
    # fmt: on

    from unicore_tpu.registry import REGISTRIES

    for registry_name, registry_info in REGISTRIES.items():
        parser.add_argument(
            "--" + registry_name.replace("_", "-"),
            default=registry_info["default"],
            choices=registry_info["registry"].keys(),
        )

    # Task definitions can be found under unicore_tpu/tasks/
    from unicore_tpu.tasks import TASK_REGISTRY

    parser.add_argument(
        "--task",
        metavar="TASK",
        default=default_task,
        choices=TASK_REGISTRY.keys(),
        help="task",
    )
    return parser


def add_dataset_args(parser, train=False, gen=False):
    group = parser.add_argument_group("Dataset and data loading")
    # fmt: off
    group.add_argument('--num-workers', default=1, type=int, metavar='N',
                       help='data-loading worker count (0 = load inline)')
    group.add_argument('--worker-impl', default='thread',
                       choices=['thread', 'process'],
                       help='data-worker pool: threads (zero-copy; '
                            'GIL-bound, fine for IO-bound record reads) or '
                            'forked worker processes (the reference '
                            'DataLoader model; use for tokenize-heavy '
                            'pipelines)')
    group.add_argument('--skip-invalid-size-inputs-valid-test', action='store_true',
                       help='drop over/under-sized examples from valid/test instead of erroring')
    group.add_argument('--batch-size', '--max-sentences', type=int, metavar='N',
                       help='number of examples in a batch PER HOST PROCESS '
                            '(all local devices of the host split it): '
                            'unlike the reference, where --batch-size is '
                            'per GPU. Porting a reference config? multiply '
                            'by the per-host device count, or use '
                            '--batch-size-per-device')
    group.add_argument('--batch-size-per-device', type=int, metavar='N',
                       help='reference-style per-device batch size; sets '
                            '--batch-size = N * local device count')
    group.add_argument('--required-batch-size-multiple', default=8, type=int, metavar='N',
                       help='round batch sizes to a multiple of N (MXU-friendly shapes)')
    group.add_argument('--data-buffer-size', default=10, type=int, metavar='N',
                       help='number of batches to preload (host->device overlap)')
    if train:
        group.add_argument('--train-subset', default='train', metavar='SPLIT',
                           help='split name to train on')
        group.add_argument('--valid-subset', default='valid', metavar='SPLIT',
                           help='comma-separated split names to validate on')
        group.add_argument('--validate-interval', type=int, default=1, metavar='N',
                           help='run validation once per N epochs')
        group.add_argument('--validate-interval-updates', type=int, default=0, metavar='N',
                           help='also run validation every N optimizer updates')
        group.add_argument('--validate-after-updates', type=int, default=0, metavar='N',
                           help='suppress validation before this many updates have run')
        group.add_argument('--fixed-validation-seed', default=None, type=int, metavar='N',
                           help='fix the eval rng stream to this seed (reproducible valid loss)')
        group.add_argument('--disable-validation', action='store_true',
                           help='never validate')
        group.add_argument('--batch-size-valid', type=int, metavar='N',
                           help='validation batch size (falls back to --batch-size)')
        group.add_argument('--max-valid-steps', type=int, metavar='N',
                           help='stop each validation run after batch index '
                                'N (i.e. N+1 batches, matching the '
                                'reference loop bound)')
        group.add_argument('--curriculum', default=0, type=int, metavar='N',
                           help='keep the batch order deterministic for the first N epochs')
        group.add_argument('--pack-sequences', action='store_true',
                           help='bin-pack variable-length samples into fixed '
                                '[B, T] rows with per-segment span metadata '
                                '(docs/performance.md#sequence-packing): '
                                'attention is segment-causal (no cross-'
                                'segment attention, positions reset per '
                                'segment) and losses mask per segment, so '
                                'packed rows train the same logical samples '
                                'as padded rows with near-zero pad waste.  '
                                'Tasks that do not implement packing ignore '
                                'the flag with a warning')
        group.add_argument('--pack-max-segments', default=0, type=int, metavar='K',
                           help='cap segments per packed row (0 = unlimited)')
    # fmt: on
    return group


def add_distributed_training_args(parser):
    group = parser.add_argument_group("Distributed training (TPU mesh)")
    # fmt: off
    group.add_argument('--distributed-world-size', type=int, metavar='N', default=None,
                       help='total number of devices across all hosts '
                            '(default: all visible devices)')
    group.add_argument('--distributed-rank', default=0, type=int,
                       help='(compat) process index; set by jax.distributed on multi-host')
    group.add_argument('--distributed-backend', default='xla', type=str,
                       help='distributed backend (XLA collectives over ICI/DCN)')
    group.add_argument('--distributed-init-method', default=None, type=str,
                       help='(compat) coordinator address, e.g. host:port — passed to '
                            'jax.distributed.initialize')
    group.add_argument('--distributed-port', default=-1, type=int,
                       help='(compat) coordinator port for multi-host init')
    group.add_argument('--device-id', '--local_rank', default=0, type=int,
                       help='(compat) single-program SPMD uses all local devices')
    group.add_argument('--distributed-no-spawn', action='store_true',
                       help='(compat) jax SPMD never spawns per-device processes')
    group.add_argument('--ddp-backend', default='spmd', type=str,
                       help='(compat) gradient reduction is compiled into the step '
                            '(accepts c10d/legacy_ddp/apex values and ignores them)')
    group.add_argument('--bucket-cap-mb', default=25, type=int, metavar='MB',
                       help='(compat) XLA schedules collectives; accepted and ignored')
    group.add_argument('--fix-batches-to-gpus', action='store_true',
                       help='(compat) deterministic shard->device mapping')
    group.add_argument('--find-unused-parameters', action='store_true',
                       help='(compat) unused params get zero grads under jax autodiff')
    group.add_argument('--fast-stat-sync', action='store_true',
                       help='(compat) stat sums ride the compiled step when the loss allows')
    group.add_argument('--broadcast-buffers', action='store_true',
                       help='(compat) no buffers outside params in the functional model')
    group.add_argument('--nprocs-per-node', type=int, default=None,
                       help='(compat) processes per node; jax uses 1 process per host')
    # TPU-mesh axes (new):
    group.add_argument('--data-parallel-size', type=int, default=-1, metavar='N',
                       help='size of the data-parallel mesh axis (-1 = all remaining devices)')
    group.add_argument('--tensor-parallel-size', type=int, default=1, metavar='N',
                       help='size of the tensor/model-parallel mesh axis: '
                            'attention/FFN weights shard Megatron-style '
                            '(heads must divide N)')
    group.add_argument('--seq-parallel-size', type=int, default=1, metavar='N',
                       help='size of the sequence/context-parallel mesh axis (ring attention)')
    group.add_argument('--pipeline-parallel-size', type=int, default=1, metavar='N',
                       help='reserved; values > 1 raise (not implemented)')
    group.add_argument('--expert-parallel-size', type=int, default=1, metavar='N',
                       help='reserved; values > 1 raise (not implemented)')
    group.add_argument('--seq-parallel-impl', choices=['ring', 'ulysses'],
                       default='ring',
                       help='sequence-parallel attention scheme when '
                            '--seq-parallel-size > 1')
    group.add_argument('--seq-parallel-skip-attention-dropout',
                       action='store_true',
                       help='accept that sequence-parallel attention does '
                            'not apply attention dropout (without this '
                            'flag, attention_dropout > 0 with '
                            '--seq-parallel-size > 1 is an error)')
    group.add_argument('--fsdp-size', type=int, default=1, metavar='N',
                       help='size of the fsdp mesh axis: master params and '
                            'optimizer state shard over it (ZeRO); the batch '
                            'shards over (data, fsdp) jointly')
    group.add_argument('--fsdp', action='store_true',
                       help='shorthand: put ALL remaining devices on the fsdp '
                            'axis (full ZeRO, no plain data axis)')
    group.add_argument('--zero1', action='store_true',
                       help='ZeRO-1 weight-update sharding on the data axis '
                            '(docs/performance.md#zero-1): grads '
                            'reduce-scatter over the data-parallel replicas, '
                            'each replica runs the optimizer update on only '
                            'its 1/N shard of the moments (created sharded — '
                            'replicated fp32 moments never materialize), and '
                            'the updated param slices all-gather back into '
                            'the replicated params.  fsdp-like optimizer '
                            'memory at near-dp communication cost; a no-op '
                            'on a 1-device data axis, so one recipe spans '
                            'laptop-CPU runs to full pods')
    group.add_argument('--comms-overlap', action='store_true',
                       help='bucketed collective scheduling for --zero1 '
                            '(docs/performance.md#collective-overlap): '
                            'master params and EMA store data-sharded like '
                            'the moments, grads reduce-scatter per size-'
                            'bounded bucket as the backward produces them, '
                            'and the only remaining gather is the step-top '
                            'bf16 compute cast — half the bytes of the fp32 '
                            'tail gather it replaces, and positioned where '
                            'XLA\'s async scheduler can hide it behind the '
                            'next step\'s early forward.  Changes reduction '
                            'order (bucketed vs monolithic), deterministically '
                            'per bucket layout.  Requires --zero1')
    group.add_argument('--comms-bucket-mb', type=float, default=4.0,
                       metavar='MB',
                       help='bucket size cap for --comms-overlap: grad '
                            'leaves fill buckets greedily in canonical tree '
                            'order up to this many MB each.  The leaf->bucket '
                            'assignment is a pure function of the param tree '
                            'and this cap, so every replica and every resume '
                            'agree on the layout')
    group.add_argument('--coordinator-address', type=str, default=None,
                       help='host:port of process 0 for jax.distributed.initialize')
    group.add_argument('--num-processes', type=int, default=None,
                       help='number of host processes for jax.distributed.initialize')
    group.add_argument('--process-id', type=int, default=None,
                       help='index of this host process for jax.distributed.initialize')
    # fmt: on
    return group


def add_optimization_args(parser):
    group = parser.add_argument_group("Optimization")
    # fmt: off
    group.add_argument('--max-epoch', '--me', default=0, type=int, metavar='N',
                       help='halt after this epoch (0 = no epoch cap)')
    group.add_argument('--max-update', '--mu', default=0, type=int, metavar='N',
                       help='halt after this many optimizer updates (0 = no cap)')
    group.add_argument('--stop-time-hours', default=0, type=float, metavar='N',
                       help='halt once cumulative wall-clock (incl. previous runs) exceeds N hours')
    group.add_argument('--clip-norm', default=0.0, type=float, metavar='NORM',
                       help='global grad-norm clip threshold (0 = off)')
    group.add_argument('--per-sample-clip-norm', default=0.0, type=float, metavar='PNORM',
                       help='per-sample grad-norm clip applied before cross-device reduction')
    group.add_argument('--update-freq', default='1', metavar='N1,N2,...,N_K',
                       type=lambda uf: utils.eval_str_list(uf, type=int),
                       help='micro-batches accumulated per optimizer update, per-epoch list')
    group.add_argument('--stats-lag', default=1, type=int, metavar='N',
                       help='process step stats N steps late so host '
                            'bookkeeping overlaps device compute (0 = '
                            'strict per-step sync; stop checks, validation '
                            'and checkpoints always see exact counts)')
    group.add_argument('--pipeline-depth', default=1, type=int, metavar='K',
                       help='multi-step pipelined dispatch: keep up to K '
                            'dispatched train steps in flight before the '
                            'host blocks on the oldest one\'s outputs. '
                            'K=1 (default — the safety off-switch for the '
                            'anomaly-ladder contract) is the classic loop, '
                            'byte-identical trajectories; K=2 is the '
                            'production setting: guard scalars, metrics and '
                            'fp16 scale decisions drain lag-K (only outputs '
                            'already on host), boundary checks ride the '
                            'drain point, and the device always holds a '
                            'queued step — step-boundary host time ~0.  '
                            'Subsumes --stats-lag at K>=2.  The anomaly '
                            'ladder stays exact: a rewind discards and '
                            'replays in-flight dispatches with their ids '
                            '(docs/performance.md#pipelined-dispatch)')
    group.add_argument('--rng-impl', default='rbg',
                       choices=['rbg', 'threefry'],
                       help='jax PRNG implementation for dropout streams: '
                            'rbg is ~13%% faster per step on TPU (measured '
                            'BERT-base v5e); threefry is the jax default '
                            'with cross-backend stream stability')
    group.add_argument('--fused-lm-head', default='on', choices=['on', 'off'],
                       help='fused chunked linear+cross-entropy head '
                            '(docs/performance.md): the loss runs the vocab '
                            'projection chunk-by-chunk so the [rows, vocab] '
                            'logits tensor never materializes in HBM — the '
                            'freed memory admits larger batches/longer '
                            'sequences.  "off" restores the materialized '
                            'head (models without the fused-head contract '
                            'always use it)')
    group.add_argument('--fused-ce-chunk', default=0, type=int, metavar='N',
                       help='rows per chunk for the fused LM/CE head; 0 = '
                            'auto: a rule on the logits\' bytes (unfused '
                            'matmul under 16 MiB of fp32 logits, else the '
                            'largest power-of-two chunk whose fp32 logits '
                            'fit 32 MiB)')
    group.add_argument('--lr', '--learning-rate', default='0.25', type=eval_str_list_float,
                       metavar='LR_1,LR_2,...,LR_N',
                       help='per-epoch learning rates; the last entry persists past the list '
                            '(schedulers may reinterpret, as in the reference CLI)')
    group.add_argument('--stop-min-lr', default=-1, type=float, metavar='LR',
                       help='halt once the scheduler drives lr to this floor (-1 = never)')
    group.add_argument('--grad-accum-dtype', default='fp32', choices=['fp32', 'bf16'],
                       help='dtype for the gradient accumulator across micro-batches')
    group.add_argument('--optim-bf16-moments', action='store_true',
                       help='store the Adam moments (exp_avg/exp_avg_sq) in '
                            'bf16 at half the optimizer-state bytes; the '
                            'update math stays fp32 and the re-quantization '
                            'uses stochastic rounding (fp32_to_bf16_sr, the '
                            'reference\'s unicore_fused_rounding op) so the '
                            'moment EMAs remain unbiased — loss-trajectory-'
                            'validated against fp32 moments '
                            '(docs/performance.md#zero-1)')
    group.add_argument('--optim-bf16-moments-rounding', default='sr',
                       choices=['sr', 'nearest'],
                       help='rounding mode for the bf16 moment store: "sr" '
                            '(stochastic, unbiased — the default and the '
                            'validated setting) or "nearest" (deterministic '
                            'round-to-nearest; biased, kept for the '
                            'trajectory-divergence comparison)')
    # fmt: on
    return group


def eval_str_list_float(x):
    return utils.eval_str_list(x, type=float)


def add_checkpoint_args(parser):
    group = parser.add_argument_group("Checkpointing")
    # fmt: off
    group.add_argument('--save-dir', metavar='DIR', default='checkpoints',
                       help='directory that receives checkpoint files')
    group.add_argument('--tmp-save-dir', metavar='DIR', default='./',
                       help='path to temporarily save checkpoints (fast local disk; a '
                            'background thread copies them into --save-dir)')
    group.add_argument('--async-save', nargs='?', const='on', default='on',
                       choices=['on', 'off'],
                       help='stream checkpoint pickling+sha256+copies to disk on a '
                            'background writer thread while training dispatch '
                            'continues (the step path pays only the device->host '
                            'capture); a failed background write surfaces at the '
                            'NEXT step boundary, and graceful shutdown drains '
                            'in-flight saves before exit-0.  "off" restores the '
                            'fully synchronous write (docs/fault_tolerance.md)')
    group.add_argument('--publish-dir', metavar='DIR', default='',
                       help='also publish a versioned weight manifest here after '
                            'every finalized save (the serve fleet watches this '
                            'directory for canary-gated live rollout, '
                            'docs/deployment.md); empty = off')
    group.add_argument('--save-queue-size', type=int, default=2, metavar='N',
                       help='max in-flight background saves before submit '
                            'blocks (backpressure: a disk slower than the save '
                            'interval stalls the step path instead of piling '
                            'state copies up in host memory)')
    group.add_argument('--restore-file', default='checkpoint_last.pt',
                       help='filename from which to load checkpoint '
                            '(default: <save-dir>/checkpoint_last.pt')
    group.add_argument('--finetune-from-model', default=None, type=str,
                       help='warm-start params from this model; optimizer/meters/lr state start fresh')
    group.add_argument('--reset-dataloader', action='store_true',
                       help='start data iteration from scratch instead of the saved position')
    group.add_argument('--reset-lr-scheduler', action='store_true',
                       help='leave the saved lr-scheduler state on disk; start the schedule over')
    group.add_argument('--reset-meters', action='store_true',
                       help='start logging meters from zero instead of the saved counters')
    group.add_argument('--reset-optimizer', action='store_true',
                       help='restore params only; optimizer moments/scaler/step start fresh')
    group.add_argument('--optimizer-overrides', default="{}", type=str, metavar='DICT',
                       help='python-dict literal of optimizer hyperparams to override at restore')
    group.add_argument('--save-interval', type=int, default=1, metavar='N',
                       help='write an epoch checkpoint once per N epochs')
    group.add_argument('--save-interval-updates', type=int, default=0, metavar='N',
                       help='also write (and validate) every N optimizer updates')
    group.add_argument('--keep-interval-updates', type=int, default=-1, metavar='N',
                       help='retain only the newest N mid-epoch (update-interval) checkpoints')
    group.add_argument('--keep-last-epochs', type=int, default=-1, metavar='N',
                       help='retain only the newest N epoch checkpoints')
    group.add_argument('--keep-best-checkpoints', type=int, default=-1, metavar='N',
                       help='retain the N best-scoring checkpoints')
    group.add_argument('--no-save', action='store_true',
                       help='disable checkpoint writing entirely')
    group.add_argument('--no-epoch-checkpoints', action='store_true',
                       help='skip per-epoch files; keep only _last and _best')
    group.add_argument('--no-last-checkpoints', action='store_true',
                       help='skip writing checkpoint_last.pt')
    group.add_argument('--no-save-optimizer-state', action='store_true',
                       help='omit optimizer moments from saved files (params only)')
    group.add_argument('--best-checkpoint-metric', type=str, default='loss',
                       help='validation stat that ranks checkpoint_best.pt')
    group.add_argument('--maximize-best-checkpoint-metric', action='store_true',
                       help='rank best checkpoints by the LARGEST value of the metric')
    group.add_argument('--patience', type=int, default=-1, metavar='N',
                       help='early stop training if valid performance doesn\'t '
                            'improve for N consecutive validation runs')
    group.add_argument('--checkpoint-suffix', type=str, default='',
                       help='string appended to every checkpoint filename')
    group.add_argument('--load-from-ema', action='store_true',
                       help='initialize params from the EMA params in the checkpoint')
    # fmt: on
    return group


def add_fault_tolerance_args(parser):
    group = parser.add_argument_group(
        "Fault tolerance (unicore_tpu/resilience; docs/fault_tolerance.md)"
    )
    # fmt: off
    group.add_argument('--anomaly-guard', action='store_true',
                       help='enable the full anomaly escalation ladder: an '
                            'anomalous step (non-finite grads, or a loss '
                            'spike past the EMA threshold) is skipped '
                            'without touching optimizer state, consecutive '
                            'anomalies back off the fp16 loss scale, rewind '
                            'to the last-good snapshot ring, and finally '
                            'abort after --anomaly-abort-after. Without the '
                            'flag: fp16 keeps the classic overflow-skip, '
                            'bf16/fp32 abort on the first non-finite step, '
                            'and spikes are only counted')
    group.add_argument('--loss-spike-factor', default=4.0, type=float,
                       metavar='K',
                       help='flag a step whose loss exceeds the running EMA '
                            'by K sigma (0 disables spike detection; '
                            'detection is always counted in metrics, but '
                            'skipping needs --anomaly-guard)')
    group.add_argument('--loss-spike-margin', default=0.0, type=float,
                       metavar='D',
                       help='absolute floor for the spike threshold (guards '
                            'against a near-zero sigma flagging benign '
                            'wiggles late in training)')
    group.add_argument('--loss-spike-window', default=64, type=int,
                       metavar='N',
                       help='EMA horizon (in clean updates) of the loss '
                            'baseline the spike rule compares against')
    group.add_argument('--loss-spike-warmup', default=16, type=int,
                       metavar='N',
                       help='clean updates before the spike rule may fire '
                            '(the EMA needs a baseline first)')
    group.add_argument('--anomaly-backoff-after', default=2, type=int,
                       metavar='N',
                       help='consecutive anomalies before the escalation '
                            'ladder force-halves the fp16 loss scale on '
                            'top of the per-overflow halving')
    group.add_argument('--anomaly-rewind-after', default=3, type=int,
                       metavar='N',
                       help='consecutive anomalies before rewinding to the '
                            'last-good snapshot ring (needs '
                            '--snapshot-interval-updates > 0)')
    group.add_argument('--anomaly-abort-after', default=6, type=int,
                       metavar='N',
                       help='consecutive anomalies before aborting the run '
                            '(log_nonfinite_modules names the first '
                            'offending module before the abort)')
    group.add_argument('--snapshot-interval-updates', default=0, type=int,
                       metavar='N',
                       help='host-copy the full TrainState every N clean '
                            'updates into the in-memory last-good ring the '
                            'rewind stage restores from (0 = off; the copy '
                            'costs one device->host fetch of the state)')
    group.add_argument('--snapshot-ring-size', default=2, type=int,
                       metavar='N',
                       help='how many last-good snapshots to keep in host '
                            'memory')
    group.add_argument('--step-timeout', default=0, type=float, metavar='SEC',
                       help='watchdog timeout on a hung device step: dump '
                            'all thread stacks + device memory stats, then '
                            'exit 87 so a supervisor restarts from the last '
                            'checkpoint (0 = off)')
    group.add_argument('--no-graceful-shutdown', action='store_true',
                       help='do NOT install the SIGTERM/SIGINT handlers '
                            'that checkpoint-and-exit at the next step '
                            'boundary on preemption')
    group.add_argument('--data-guard', action='store_true',
                       help='enable the input-pipeline fault ladder: '
                            'transient IO errors in dataset reads retry '
                            'with bounded backoff, an irrecoverably '
                            'corrupt sample is replaced by a seeded '
                            'deterministic resample (bit-exact across '
                            'resume; skip decisions ride the checkpoint), '
                            'and a corrupt-rate budget escalates '
                            'skip -> warn -> abort.  Without the flag a '
                            'corrupt record raises DataIntegrityError at '
                            'first touch (typed, never silently-truncated '
                            'tensors) and kills the run')
    group.add_argument('--data-retries', default=2, type=int, metavar='N',
                       help='transient-IO retries per dataset read before '
                            'the guard escalates it as an integrity '
                            'failure (exponential backoff between tries)')
    group.add_argument('--data-retry-backoff', default=0.05, type=float,
                       metavar='SEC',
                       help='base backoff between dataset-read retries '
                            '(doubles per attempt)')
    group.add_argument('--data-corrupt-budget', default=0.01, type=float,
                       metavar='RATE',
                       help='abort once the corrupt-sample rate (unique '
                            'skips / samples fetched) exceeds this; warns '
                            'at half the budget (0 disables the '
                            'abort rung)')
    group.add_argument('--data-resample-attempts', default=8, type=int,
                       metavar='N',
                       help='seeded replacement draws per corrupt sample '
                            'before giving up (each draw that lands on '
                            'another corrupt record burns one attempt)')
    group.add_argument('--trajectory-file', default=None, metavar='FILE',
                       help='append one JSON line per processed update '
                            '(exact float loss, skip/escalation action) — '
                            'the bit-exact evidence tools/unicore_chaos.py '
                            'compares between a killed-and-resumed run and '
                            'its uninterrupted oracle')
    # fmt: on
    return group


def add_common_eval_args(group):
    # fmt: off
    group.add_argument('--path', metavar='FILE',
                       help='colon-separated list of model checkpoint paths')
    group.add_argument('--quiet', action='store_true',
                       help='print nothing but the final scores')
    group.add_argument('--model-overrides', default="{}", type=str, metavar='DICT',
                       help='python-dict literal of model args to override at eval time')
    group.add_argument('--results-path', metavar='RESDIR', type=str, default=None,
                       help='where to write eval outputs (omit to skip)')
    # fmt: on


def add_model_args(parser):
    group = parser.add_argument_group("Model configuration")
    # fmt: off
    from unicore_tpu.models import ARCH_MODEL_REGISTRY
    group.add_argument('--arch', '-a', metavar='ARCH',
                       choices=ARCH_MODEL_REGISTRY.keys(),
                       help='architecture preset name')
    # fmt: on
    return group
