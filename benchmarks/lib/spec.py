"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell is one entry of ``workloads``.  Everything that belongs to one
cell, one configuration, one traffic mix or one per-layer metric sits in
a file of its own, so a later PR adds files and entries and edits none:

    benchmarks/workloads/<cell>.json        runner, limits, expected kernels
    <configs[].file>                        the configuration as it is run
    benchmarks/traffic/<traffic>.json       parameters of the one generator
    benchmarks/layer_metrics/<metric>.py    a reader: ``read(ctx) -> number | None``
    benchmarks/families/<family>.py         what one family of models needs:
                                            its builder or flags, its reference
"""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class SpecError(Exception):
    pass


def _json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no such file: {path}") from None


def _one(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SpecError(
            f"{what} {name!r}: {len(found)} entries in BENCHMARK.json "
            f"(known: {sorted(e['name'] for e in entries)})")
    return found[0]


def load_benchmark(root=ROOT):
    return _json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name, root=ROOT):
    """The cell ``name`` with its workload, configuration and traffic
    files read in: ``{entry, workload, config_entry, config, traffic,
    end_to_end, per_layer}``.  The two metric lists hold the metrics this
    cell reports."""
    bench = load_benchmark(root)
    entry = _one(bench["workloads"], name, "workload")
    config_entry = _one(bench["configs"], entry["config"], "config")
    paths = bench["paths"]
    base = paths[0]

    def mine(metric):
        cells = metric.get("workloads")
        return cells is None or name in cells

    config = _json(os.path.join(root, config_entry["file"]))
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m) and m["moves"] in e2e_names]
    return {
        "name": name,
        "root": root,
        "base": os.path.join(root, base),
        "entry": entry,
        "workload": _json(os.path.join(root, base, "workloads", name + ".json")),
        "config_entry": config_entry,
        "config": config,
        "family": load_family(config["family"], os.path.join(root, base)),
        "traffic": _json(os.path.join(root, base, "traffic",
                                      entry["traffic"] + ".json")),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def _module(path, what):
    if not os.path.exists(path):
        raise SpecError(f"{what} has no file at {path}")
    stem = os.path.basename(path)[:-3].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location("bench_file_" + stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric_name, base):
    """The ``read(ctx)`` of ``layer_metrics/<metric_name>.py``."""
    path = os.path.join(base, "layer_metrics", metric_name + ".py")
    mod = _module(path, f"per-layer metric {metric_name!r}")
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return mod.read


def load_family(name, base):
    """The module ``families/<name>.py``: what the harness has to know
    of one family of models (the configuration's ``"family"``)."""
    return _module(os.path.join(base, "families", name + ".py"),
                   f"family {name!r}")


def load_peaks(device_kind, base=os.path.join(ROOT, "benchmarks")):
    """The published peaks of ``device_kind``; a device that is not in
    the table is an error, not a default."""
    table = _json(os.path.join(base, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(
            f"no peaks recorded for device_kind {device_kind!r} in "
            f"peaks.json (known: {sorted(table)}); add it with its source")
    return table[device_kind]
