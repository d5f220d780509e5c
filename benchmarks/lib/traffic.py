"""The one generator of traffic.  A traffic mix is a data file of
parameters under ``benchmarks/traffic/``; a new mix is a new file.

Every seed gets the SAME sizes and arrival gaps in the SAME order, drawn
once from ``SHAPE_SEED``, with other tokens: so two seeds do the same
work.  (An order drawn from the seed was tried: which request meets which
in a queue then moves a tail more than any bound can hold, ``PERF.md``.)

kinds
-----
``train_corpus``   a corpus of token records for the train entry point
``open_loop``      independent users: requests on a schedule (Poisson gaps)
``closed_loop``    ``clients`` callers, each waiting for its reply: sessions
                   of several asks over one shared document
"""

import math
import os

import numpy as np

FIRST_TOKEN = 4  # ids 0..3 are the dictionary's specials (pad among them)
SHAPE_SEED = 0   # sizes and gaps are drawn from this, whatever --seed is
POOL = 256       # sizes a closed-loop mix cycles through


def _draw(rng, spec, n):
    """``n`` whole numbers from a length distribution ``spec``."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif dist == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n).astype(float)
    elif dist == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    if "min" in spec:
        x = np.clip(x, spec["min"], spec["max"])
    return np.rint(x).astype(np.int64)


def _tokens(rng, n, vocab):
    return rng.integers(FIRST_TOKEN, vocab, n).tolist()


# -- training -----------------------------------------------------------

def corpus_records(spec, seed, n_records, symbols):
    """Token-id rows of a Zipf corpus: lengths from ``spec['length']``,
    the same sequence of them for every seed (the program's own data
    order is fixed too, so every seed trains on batches of the same
    sizes and the count of real tokens in a window does not vary with
    the draw); ids from the seed, by inverse CDF of p(rank) ~ 1 / rank."""
    shape_rng = np.random.default_rng(SHAPE_SEED)
    lengths = _draw(shape_rng, spec["length"], n_records)
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, symbols + 1) ** spec.get("zipf_exponent", 1.0)
    cdf = np.cumsum(p / p.sum())
    flat = np.searchsorted(cdf, rng.random(int(lengths.sum())))
    flat = np.minimum(flat, symbols - 1)
    return np.split(flat, np.cumsum(lengths)[:-1])


def write_corpus(data_dir, spec, seed, n_train, symbols, writer_cls):
    """``dict.txt`` and ``{train,valid}.rec`` in the program's own record
    format (``writer_cls`` is its writer): rows of symbol strings."""
    os.makedirs(data_dir, exist_ok=True)
    words = np.asarray(["w%d" % i for i in range(symbols)], dtype=object)
    with open(os.path.join(data_dir, "dict.txt"), "w") as f:
        f.write("".join(f"{w} {symbols - i}\n" for i, w in enumerate(words)))
    rows = corpus_records(spec, seed, n_train + 8, symbols)
    for split, part in (("train", rows[:n_train]), ("valid", rows[n_train:])):
        with writer_cls(os.path.join(data_dir, split + ".rec")) as w:
            for row in part:
                w.write(words[row].tolist())
    return sum(len(r) for r in rows[:n_train])


# -- serving ------------------------------------------------------------

def open_loop_schedule(spec, seed, horizon_s, vocab):
    """Requests due at Poisson times over ``horizon_s`` seconds at
    ``spec['rate_per_s']``: ``[{due_s, prompt, max_new_tokens, id}]``.
    The count, the gaps, the lengths and their order are the same for
    every seed; the tokens are the seed's."""
    shape_rng = np.random.default_rng(SHAPE_SEED)
    n = max(1, int(round(spec["rate_per_s"] * horizon_s)))
    gaps = shape_rng.exponential(1.0 / spec["rate_per_s"], n)
    gaps *= horizon_s / gaps.sum()  # the same span, whatever was drawn
    prompts = _draw(shape_rng, spec["prompt"], n)
    answers = _draw(shape_rng, spec["answer"], n)
    rng = np.random.default_rng(seed)
    due = np.cumsum(gaps)
    return [{"id": f"r{i}",
             "due_s": float(due[i]),
             "prompt": _tokens(rng, int(prompts[i]), vocab),
             "max_new_tokens": int(answers[i])} for i in range(n)]


class ClosedLoopSessions:
    """``clients`` callers; each holds one document and asks
    ``asks_per_document`` questions about it, one after the other, then
    takes the next document.  ``next_request(client)`` is the client's
    next ask; the caller sends it when the previous one has come back."""

    def __init__(self, spec, seed, vocab):
        self.spec, self.vocab = spec, vocab
        shape_rng = np.random.default_rng(SHAPE_SEED)
        self._docs = _draw(shape_rng, spec["document"], POOL)
        self._questions = _draw(shape_rng, spec["question"], POOL * 8)
        self._answers = _draw(shape_rng, spec["answer"], POOL * 8)
        self.rng = np.random.default_rng(seed)
        self._next_doc = 0
        self._next_ask = 0
        self.clients = [None] * spec["clients"]
        self.documents_opened = 0

    def next_request(self, client):
        state = self.clients[client]
        if state is None or state["asked"] >= self.spec["asks_per_document"]:
            n = int(self._docs[self._next_doc % len(self._docs)])
            self._next_doc += 1
            self.documents_opened += 1
            state = self.clients[client] = {
                "doc": _tokens(self.rng, n, self.vocab), "asked": 0,
                "doc_id": self.documents_opened}
        q = int(self._questions[self._next_ask % len(self._questions)])
        a = int(self._answers[self._next_ask % len(self._answers)])
        self._next_ask += 1
        state["asked"] += 1
        return {
            "id": f"c{client}d{state['doc_id']}a{state['asked']}",
            "client": client,
            "prompt": state["doc"] + _tokens(self.rng, q, self.vocab),
            "max_new_tokens": a,
            "shared_tokens": len(state["doc"]) if state["asked"] > 1 else 0,
        }
