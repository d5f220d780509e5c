"""The toy hybrid cell (two periods of linear-attention and full-attention
layers at width 64), ADDED to the root ``bench_tiny.make_root`` builds,
the same way: new files plus entries, no file that was there edited."""

import json
import os
import shutil

import bench_tiny

CELL, CONFIG, MIX, LIKE = ("tiny_longdocs", "tiny_hybrid", "tiny_longdocs",
                           "olmo_hybrid_longdocs")


def make_root(tmp):
    root = bench_tiny.make_root(tmp)
    base = os.path.join(root, "benchmarks")
    before = bench_tiny._listing(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(bench_tiny.TINY, f"config_{CONFIG}.json"),
                os.path.join(base, "configs", CONFIG + ".json"))
    shutil.copy(os.path.join(bench_tiny.TINY, f"traffic_{MIX}.json"),
                os.path.join(base, "traffic", MIX + ".json"))
    shutil.copy(os.path.join(bench_tiny.TINY, f"workload_{CELL}.json"),
                os.path.join(base, "workloads", CELL + ".json"))
    bench["configs"].append({
        "name": CONFIG, "source": "tests", "reduced": [], "why": "tests",
        "file": f"benchmarks/configs/{CONFIG}.json"})
    bench["workloads"].append({"name": CELL, "config": CONFIG,
                               "traffic": MIX, "chips": 1, "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = bench_tiny._listing(root)
    changed = [p for p in before if p != "BENCHMARK.json"
               and before[p] != after.get(p)]
    assert not changed, f"adding the hybrid cell edited {changed}"
    return root
