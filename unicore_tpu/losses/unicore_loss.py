"""Loss base class.

The reference's ``UnicoreLoss`` (``unicore/losses/unicore_loss.py:14``) is an
``nn.Module`` whose ``forward(model, sample)`` returns
``(loss, sample_size, logging_output)``.  The TPU-native contract is a pure
function suitable for tracing inside the jitted train step::

    loss, sample_size, logging_output = loss.forward(
        model, params, sample, rng=key, is_training=True)

- ``loss`` is a scalar jnp array (the *sum* over the micro-batch, matching
  the reference where grads are later normalized by the aggregated
  sample_size — trainer.py:695-709).
- ``sample_size`` is a scalar (python int or jnp) used for that
  normalization.
- ``logging_output`` is a flat dict of scalar jnp arrays. When
  ``logging_outputs_can_be_summed()`` is True they are summed across
  micro-batches and data-parallel shards inside the compiled step (the
  analogue of the reference's fast ``all_reduce_dict`` path,
  trainer.py:973-1055).
"""


def fused_head_request(loss, model):
    """``(want_fused, chunk_override)`` for a loss about to call
    ``model.apply``: the fused chunked linear+cross-entropy head
    (``ops/fused_cross_entropy.py``) is requested when ``--fused-lm-head``
    is not "off" (the default is on) AND the model declares
    ``supports_fused_head`` (the features+kernel+bias output contract) —
    models without the contract silently keep the materialized-logits
    path.  ``chunk_override`` is ``--fused-ce-chunk`` (0/None = auto:
    the op's byte rule)."""
    args = getattr(loss, "args", None)
    enabled = str(getattr(args, "fused_lm_head", None) or "on") != "off"
    if not (enabled and getattr(model, "supports_fused_head", False)):
        return False, None
    chunk = int(getattr(args, "fused_ce_chunk", 0) or 0)
    return True, (chunk if chunk > 0 else None)


class UnicoreLoss:
    def __init__(self, task):
        self.task = task
        self.args = task.args if task is not None else None

    @classmethod
    def add_args(cls, parser):
        """Add loss-specific arguments to the parser."""
        pass

    @classmethod
    def build_loss(cls, args, task):
        """Construct a loss from command-line args."""
        return cls(task)

    def forward(self, model, params, sample, rng=None, is_training=True):
        """Compute the loss for the given sample.

        Returns a tuple ``(loss, sample_size, logging_output)``.
        """
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        return self.forward(*args, **kwargs)

    @staticmethod
    def reduce_metrics(logging_outputs, split="train") -> None:
        """Aggregate logging outputs from data-parallel training into the
        global metrics aggregators (host-side)."""
        raise NotImplementedError

    @staticmethod
    def logging_outputs_can_be_summed(is_train: bool) -> bool:
        """Whether the logging outputs returned by ``forward`` can be summed
        across workers prior to calling ``reduce_metrics``. Setting this
        to True keeps stat aggregation inside the compiled step (fast path).
        """
        return False
