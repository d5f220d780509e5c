"""The harness is driven by data: a configuration, a traffic mix, a cell
and a per-layer metric are each added as new files plus entries, with no
edit to a file that is there (``bench_tiny.make_root`` adds them and
asserts that nothing else changed)."""

import json
import os
import re

import pytest

import bench_tiny
from benchmarks.lib import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", sorted(bench_tiny.CELLS))
def test_an_added_cell_is_found_with_its_files(root, cell):
    got = spec.load_cell(cell, root)
    cfg, mix, _ = bench_tiny.CELLS[cell]
    assert got["entry"]["config"] == cfg and got["entry"]["traffic"] == mix
    assert got["config"]["hidden_size"] == 64          # the added config
    assert got["traffic"]["kind"] in ("train_corpus", "open_loop",
                                      "closed_loop")  # the added mix
    assert got["workload"]["runner"] in ("train", "serve")
    names = {m["name"] for m in got["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert got["per_layer"]


def test_an_added_metric_is_found_by_name_and_only_in_its_cells(root):
    mlm = spec.load_cell("tiny_mlm", root)
    assert "window_updates" in {m["name"] for m in mlm["per_layer"]}
    read = spec.load_reader("window_updates", mlm["base"])
    assert read({"updates": 7}) == 7 and read({}) is None
    chat = spec.load_cell("tiny_chat", root)
    assert "window_updates" not in {m["name"] for m in chat["per_layer"]}


def test_an_added_family_is_found_by_the_name_its_configuration_gives(root):
    toy = spec.load_cell("toy_cell", root)
    assert toy["family"].dims(toy["config"]) == {"layers": 3}
    mlm = spec.load_cell("tiny_mlm", root)
    assert callable(mlm["family"].train_argv)
    assert callable(spec.load_cell("tiny_chat", root)["family"].build_model)


def test_the_committed_cells_still_load_from_the_grown_root(root):
    for name in ("bert_base_mlm", "opt13b_chat", "opt13b_docs_batch"):
        assert spec.load_cell(name, root)["name"] == name


@pytest.mark.parametrize("call,err", [
    (lambda r: spec.load_cell("no_such_cell", r), "no_such_cell"),
    (lambda r: spec.load_reader("no_such_metric",
                                os.path.join(r, "benchmarks")),
     "no_such_metric"),
    (lambda r: spec.load_peaks("TPU v99", os.path.join(r, "benchmarks")),
     "TPU v99"),
    (lambda r: spec.load_family("no_such_family",
                                os.path.join(r, "benchmarks")),
     "no_such_family"),
])
def test_what_is_not_there_is_an_error_not_a_default(root, call, err):
    with pytest.raises(spec.SpecError, match=err):
        call(root)


def test_the_peaks_of_the_v5e():
    p = spec.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9 and p["ici_bits_per_s"] == 1600e9


def test_every_reader_a_cell_needs_exists():
    bench = spec.load_benchmark()
    base = os.path.join(spec.ROOT, bench["paths"][0])
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"], base))


def test_benchmark_json_keeps_the_contracts_form():
    b = spec.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) \
        == len(cells)
    assert sum(w["chips"] == 4 for w in cells.values()) \
        <= max(1, len(cells) // 4)
    for w in cells.values():
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert set(m.get("workloads", ())) <= set(cells)
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", cells)
    # every cell reports setup_s, another end-to-end metric and a layer one
    for name in cells:
        loaded = spec.load_cell(name)
        assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]
    for c in b["configs"]:
        path = os.path.join(spec.ROOT, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
