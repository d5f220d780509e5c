"""The one generator of traffic: reproducible from a seed, the same work
for every seed."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import traffic

MIXES = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                     "traffic")


def mix(name, **changes):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return {**json.load(f), **changes}


def test_open_loop_is_reproducible_and_large_seeds_work():
    spec = mix("chat_poisson")
    a = traffic.open_loop_schedule(spec, 2 ** 31 + 11, 30.0, 50272)
    b = traffic.open_loop_schedule(spec, 2 ** 31 + 11, 30.0, 50272)
    assert a == b
    assert a != traffic.open_loop_schedule(spec, 12, 30.0, 50272)


def test_every_seed_gets_the_same_schedule_with_other_tokens():
    spec = mix("chat_poisson")
    a = traffic.open_loop_schedule(spec, 1, 30.0, 50272)
    b = traffic.open_loop_schedule(spec, 2, 30.0, 50272)
    assert [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in a] \
        == [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    docs = mix("docs_closed10")
    x, y = (traffic.ClosedLoopSessions(docs, s, 50272) for s in (1, 2))
    rx, ry = x.next_request(0), y.next_request(0)
    assert len(rx["prompt"]) == len(ry["prompt"]) and rx != ry
    assert rx["max_new_tokens"] == ry["max_new_tokens"]


def test_open_loop_keeps_the_rate_the_span_and_the_clipped_lengths():
    spec = mix("chat_poisson")
    runs = [traffic.open_loop_schedule(spec, s, 30.0, 50272)
            for s in (1, 2, 3)]
    for run in runs:
        due = [r["due_s"] for r in run]
        assert due == sorted(due) and due[-1] == pytest.approx(30.0)
        assert len(run) == round(spec["rate_per_s"] * 30.0)
        lo, hi = spec["prompt"]["min"], spec["prompt"]["max"]
        assert all(lo <= len(r["prompt"]) <= hi for r in run)
        assert all(4 <= t < 50272 for r in run for t in r["prompt"])
        assert [r["id"] for r in run] == [f"r{i}" for i in range(len(run))]


def test_closed_loop_sessions_share_their_document():
    spec = mix("docs_closed10")
    s = traffic.ClosedLoopSessions(spec, 9, 50272)
    asks = [s.next_request(0) for _ in range(spec["asks_per_document"] + 1)]
    first, second, nxt = asks[0], asks[1], asks[-1]
    doc = first["prompt"][:len(first["prompt"])
                          - (len(first["prompt"]) - _doc_len(first, second))]
    assert second["prompt"][:len(doc)] == doc
    assert first["shared_tokens"] == 0 and second["shared_tokens"] == len(doc)
    assert spec["document"]["min"] <= len(doc) <= spec["document"]["max"]
    # the fifth ask opens a new document
    assert nxt["shared_tokens"] == 0 and nxt["prompt"][:64] != doc[:64]
    assert s.documents_opened == 2
    # another client has a document of its own
    assert s.next_request(1)["prompt"][:64] != nxt["prompt"][:64]


def _doc_len(a, b):
    n = 0
    for x, y in zip(a["prompt"], b["prompt"]):
        if x != y:
            break
        n += 1
    return n


def test_closed_loop_is_reproducible():
    spec = mix("docs_closed10")

    def play(seed):
        s = traffic.ClosedLoopSessions(spec, seed, 50272)
        return [s.next_request(c % spec["clients"]) for c in range(25)]

    assert play(4) == play(4)
    assert play(4) != play(5)


def test_corpus_rows_are_zipf_and_the_same_lengths_for_every_seed():
    spec = mix("mlm_b64_s512")
    a = traffic.corpus_records(spec, 1, 400, 30517)
    b = traffic.corpus_records(spec, 2, 400, 30517)
    assert sorted(map(len, a)) == sorted(map(len, b))
    assert all(256 <= len(r) <= 511 for r in a)
    flat = np.concatenate(a)
    assert flat.min() >= 0 and flat.max() < 30517
    assert (flat == 0).mean() > (flat == 100).mean() > 0  # rank 1 over 101
    again = traffic.corpus_records(spec, 1, 400, 30517)
    assert all((x == y).all() for x, y in zip(a, again))


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(MIXES) if f.endswith(".json")))
def test_every_committed_mix_names_a_known_kind(name):
    assert mix(name)["kind"] in ("train_corpus", "open_loop", "closed_loop")
