"""Causal decoder language model example plugin (``--user-dir examples/lm``).

Demonstrates the full plugin surface: a task, a model family built on
``TransformerDecoder``, an ARCH preset set, and a loss registered from
user code.  The reference ships only the BERT example; this exercises the
decoder stack end-to-end the same way.
"""

from . import hybrid, laguna, lfm2_moe, loss, model, pangu_moe, task  # noqa: F401 — trigger @register_* decorators
