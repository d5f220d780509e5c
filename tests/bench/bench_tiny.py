"""A benchmark root for the tests: the committed ``BENCHMARK.json`` and
``benchmarks/`` copied to a scratch directory, then toy-width cells ADDED
to it as new files and new entries, no file that was there edited.  That
a cell, a configuration, a traffic mix and a per-layer metric can be added
so is what the loader tests check; the cells themselves let the CPU tests
drive a whole run."""

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny")

# toy cell: (its configuration, its traffic mix, the committed cell whose
# metrics it reports)
CELLS = {
    "tiny_mlm": ("tiny_bert", "tiny_mlm", "bert_base_mlm"),
    "tiny_chat": ("tiny_lm", "tiny_chat", "opt13b_chat"),
    "tiny_docs": ("tiny_lm", "tiny_docs", "opt13b_docs_batch"),
}


def make_root(tmp):
    root = os.path.join(str(tmp), "root")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _listing(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    base = os.path.join(root, "benchmarks")
    for cfg in ("tiny_bert", "tiny_lm"):
        shutil.copy(os.path.join(TINY, f"config_{cfg}.json"),
                    os.path.join(base, "configs", cfg + ".json"))
        bench["configs"].append({
            "name": cfg, "source": "tests", "reduced": [], "why": "tests",
            "file": f"benchmarks/configs/{cfg}.json"})
    for cell, (cfg, mix, like) in CELLS.items():
        shutil.copy(os.path.join(TINY, f"traffic_{mix}.json"),
                    os.path.join(base, "traffic", mix + ".json"))
        shutil.copy(os.path.join(TINY, f"workload_{cell}.json"),
                    os.path.join(base, "workloads", cell + ".json"))
        bench["workloads"].append({"name": cell, "config": cfg,
                                   "traffic": mix, "chips": 1, "why": "tests"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    # a family of its own, as a later PR that brings another model would
    # add one: a file, named by its configuration
    with open(os.path.join(base, "families", "toy_family.py"), "w") as f:
        f.write('"""A family the tests add."""\n\n\n'
                "def dims(cfg):\n    return {'layers': cfg['n_layer']}\n")
    with open(os.path.join(base, "configs", "toy.json"), "w") as f:
        json.dump({"family": "toy_family", "n_layer": 3, "reduced": {}}, f)
    with open(os.path.join(base, "workloads", "toy_cell.json"), "w") as f:
        json.dump({"runner": "train", "limits": {}}, f)
    bench["configs"].append({"name": "toy", "source": "tests", "reduced": [],
                             "why": "tests",
                             "file": "benchmarks/configs/toy.json"})
    bench["workloads"].append({"name": "toy_cell", "config": "toy",
                               "traffic": "tiny_mlm", "chips": 1,
                               "why": "tests"})
    # a per-layer metric of its own, as a later PR would add one
    with open(os.path.join(base, "layer_metrics", "window_updates.py"),
              "w") as f:
        f.write('"""Updates in the traced window."""\n\n\n'
                "def read(ctx):\n    return ctx.get('updates')\n")
    bench["per_layer"].append({
        "name": "window_updates", "unit": "updates", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tokens_per_s", "workloads": ["tiny_mlm"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _listing(root)
    changed = [p for p in before if p != "BENCHMARK.json"
               and before[p] != after.get(p)]
    assert not changed, f"adding cells edited {changed}"
    return root


def _listing(root):
    out = {}
    for d, _, files in os.walk(root):
        for name in files:
            p = os.path.join(d, name)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hash(f.read())
    return out
