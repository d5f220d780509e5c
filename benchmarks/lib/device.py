"""The chip: is it there, what is it, how much memory did it use."""

class NoChip(Exception):
    pass


def require_tpu(chips):
    """The devices of the run, or :class:`NoChip`: a measurement path
    that finds no chip fails, it never falls back to the CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"jax found {platform!r} ({devices[0].device_kind}), "
                     "not a tpu; nothing was run")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), jax sees "
                     f"{len(devices)}; nothing was run")
    return devices[:chips]


def describe(devices):
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest device (0 where the backend
    reports none, as the CPU does)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Compiles:
    """jax's own monitoring events: the seconds of every backend
    compile and the persistent cache's hits.  ``count()`` between two
    marks says whether anything compiled in the window."""

    _instance = None

    def __init__(self):
        self.seconds = []
        self.hits = 0
        self.stages = {}  # seconds by stage: tracing, lowering, compiling

    @classmethod
    def listen(cls):
        if cls._instance is None:
            import jax

            cls._instance = cls()
            jax.monitoring.register_event_listener(cls._instance._event)
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._duration)
        return cls._instance

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds.append(duration)
        if event.startswith("/jax/core/compile/"):
            stage = event.rsplit("/", 1)[-1]
            self.stages[stage] = self.stages.get(stage, 0.0) + duration

    def count(self):
        return len(self.seconds)


def configure_compile_cache():
    """The program's own placement of jax's persistent cache (the
    directory ``JAX_COMPILATION_CACHE_DIR`` names, else
    ``<checkout>/.jax_cache``), with every program of a run cached, the
    small ones too: after the first run in a checkout nothing compiles."""
    import jax

    import unicore_tpu.utils as program_utils

    path = program_utils.configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def log(*parts):
    """Earlier lines of a run: everything worth reading that is not the
    result line.  To stdout, flushed, so it interleaves with the
    program's own log in order."""
    print("bench:", *parts, flush=True)
