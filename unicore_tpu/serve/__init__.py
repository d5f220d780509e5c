"""``unicore_tpu.serve`` — continuous-batching inference over a paged
KV-cache pool (docs/serving.md).

Layering: this package sits ON TOP of the module stack (the attention
modules grow a ``paged=`` entry point that calls back into
``serve.attention``), so the package init stays lazy — importing a
module that merely touches the paged entry point must not pull jitted
engine machinery."""

_EXPORTS = {
    "PagedKVPool": ("unicore_tpu.serve.kv_pool", "PagedKVPool"),
    "PoolExhausted": ("unicore_tpu.serve.kv_pool", "PoolExhausted"),
    "PagedMeta": ("unicore_tpu.serve.attention", "PagedMeta"),
    "paged_attention": ("unicore_tpu.serve.attention", "paged_attention"),
    "paged_attention_reference": (
        "unicore_tpu.serve.attention", "paged_attention_reference"),
    "Request": ("unicore_tpu.serve.scheduler", "Request"),
    "Scheduler": ("unicore_tpu.serve.scheduler", "Scheduler"),
    "ServeEngine": ("unicore_tpu.serve.engine", "ServeEngine"),
    "ServeResult": ("unicore_tpu.serve.engine", "ServeResult"),
    "sample_token": ("unicore_tpu.serve.sampling", "sample_token"),
    "sample_tokens": ("unicore_tpu.serve.sampling", "sample_tokens"),
    "step_key": ("unicore_tpu.serve.sampling", "step_key"),
    "finite_rows": ("unicore_tpu.serve.sampling", "finite_rows"),
    "reject_newest": ("unicore_tpu.serve.scheduler", "reject_newest"),
    "step_logs": ("unicore_tpu.serve.step_log", "step_logs"),
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    try:
        mod_name, attr = _EXPORTS[name]
    except KeyError:
        raise AttributeError(name) from None
    import importlib

    return getattr(importlib.import_module(mod_name), attr)
