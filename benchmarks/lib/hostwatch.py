"""What the host was doing while a window ran: the evidence for a run
that reads far off.

A run's window sometimes holds a stall of seconds (``PERF.md``, PR 23).
The numbers say that it happened, not why.  So every window is watched
from a thread that does nothing but sleep ``TICK`` seconds and note when
it woke, and once a second the machine's and the process's CPU clocks
(``/proc/stat``, ``/proc/pressure/cpu``, ``os.times``).  After the
window, :meth:`Watch.report` puts these beside the longest hold-up the
main loop saw:

* the ticker woke late as well, the machine's CPUs were idle or stolen:
  the guest was not running (the machine);
* the ticker woke on time, the process burnt CPU: the main thread was
  busy in the host's own code (the harness or the program);
* the ticker woke on time, the process slept: the main thread waited for
  the device or for the runtime's threads.

The thread takes the GIL for a few microseconds ten times a second.
"""

import gc
import os
import resource
import threading
import time

TICK = 0.1
CPU_FIELDS = ("user", "nice", "system", "idle", "iowait", "irq", "softirq",
              "steal")


def _proc_stat():
    """Seconds of every kind the machine's CPUs have spent, summed over
    the CPUs, or {} where ``/proc/stat`` is missing or counts nothing (a
    sandboxed kernel, as on the machines with the chip)."""
    try:
        with open("/proc/stat") as f:
            parts = f.readline().split()
    except OSError:
        return {}
    hz = os.sysconf("SC_CLK_TCK")
    out = {k: int(v) / hz for k, v in zip(CPU_FIELDS, parts[1:])}
    return out if any(out.values()) else {}


def _pressure():
    """Seconds some task has waited for a CPU (PSI), or None."""
    try:
        with open("/proc/pressure/cpu") as f:
            for line in f:
                if line.startswith("some"):
                    return int(line.rsplit("total=", 1)[1]) / 1e6
    except (OSError, ValueError, IndexError):
        pass
    return None


def _snapshot():
    t = os.times()
    return {"at": time.perf_counter(), "machine": _proc_stat(),
            "pressure_s": _pressure(), "process_cpu_s": t.user + t.system}


def _delta(a, b):
    out = {"process_cpu_s": b["process_cpu_s"] - a["process_cpu_s"]}
    for k in a["machine"]:
        out[k] = b["machine"][k] - a["machine"][k]
    if a["pressure_s"] is not None and b["pressure_s"] is not None:
        out["cpu_pressure_s"] = b["pressure_s"] - a["pressure_s"]
    return {k: round(v, 3) for k, v in out.items()}


class Watch:
    def __init__(self):
        self.ticks = []
        self.samples = []
        self.gc_pauses = []
        self._gc_t0 = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-hostwatch")
        self._usage0 = None

    def start(self):
        self._usage0 = resource.getrusage(resource.RUSAGE_SELF)
        self.samples.append(_snapshot())
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self.samples.append(_snapshot())

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_pauses.append((self._gc_t0, time.perf_counter()
                                   - self._gc_t0, info.get("generation")))
            self._gc_t0 = None

    def _run(self):
        every = max(1, round(1.0 / TICK))
        n = 0
        while not self._stop.wait(TICK):
            self.ticks.append(time.perf_counter())
            n += 1
            if n % every == 0:
                self.samples.append(_snapshot())

    # -- after the window ------------------------------------------------

    def late_ticks(self, least=0.25):
        """``(when, seconds)`` of every wake-up that came ``least``
        seconds or more after the one before."""
        t = [self.samples[0]["at"]] + self.ticks
        return [(b, b - a) for a, b in zip(t, t[1:]) if b - a >= least]

    def between(self, t0, t1):
        """What the clocks counted over the samples that enclose
        ``[t0, t1]``."""
        before = [s for s in self.samples if s["at"] <= t0]
        after = [s for s in self.samples if s["at"] >= t1]
        a = before[-1] if before else self.samples[0]
        b = after[0] if after else self.samples[-1]
        return {"sampled_s": round(b["at"] - a["at"], 3), **_delta(a, b)}

    def report(self, log, t_open, holdups):
        """Log the window's totals and, for each of ``holdups`` (``(start,
        seconds, what)`` on ``perf_counter``'s clock, the longest the main
        loop saw), what the clocks say of that stretch."""
        u0, u1 = self._usage0, resource.getrusage(resource.RUSAGE_SELF)
        first, last = self.samples[0], self.samples[-1]
        late = self.late_ticks()
        log(f"host watch: {last['at'] - first['at']:.2f} s on "
            f"{os.cpu_count()} CPUs; seconds by the machine's clocks "
            f"{_delta(first, last)}; switched out "
            f"{u1.ru_nivcsw - u0.ru_nivcsw} times, major faults "
            f"{u1.ru_majflt - u0.ru_majflt}; gc pauses {len(self.gc_pauses)}"
            f" summing {sum(p[1] for p in self.gc_pauses):.3f} s, longest "
            f"{max((p[1] for p in self.gc_pauses), default=0.0):.3f} s; "
            f"{len(self.ticks)} ticks, late ones (at s, late by s) "
            f"{[(round(w - t_open, 2), round(d, 2)) for w, d in late[:8]]}")
        for start, seconds, what in holdups:
            ticks_late = sum(d for w, d in late
                             if start <= w <= start + seconds + TICK)
            gc_s = sum(p[1] for p in self.gc_pauses
                       if start <= p[0] <= start + seconds)
            log(f"host watch: hold-up of {seconds:.3f} s at "
                f"{start - t_open:.2f} s ({what}): ticker late by "
                f"{ticks_late:.2f} s in it, gc {gc_s:.3f} s, clocks "
                f"{self.between(start, start + seconds)}")
