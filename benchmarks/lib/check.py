"""The comparison that decides ``correct``.

Every number compared has a limit of its own, read from the cell's
workload file (``limits``); a run prints each number beside its limit.
The limits were set from readings on the chip (``PERF.md`` gives them):
above the largest a sound run gave, below the smallest the control gave.
"""

import math

import numpy as np

from . import weights
from .serve_cell import OK_REASONS
from .device import log


class Verdict:
    def __init__(self):
        self.rows = []   # (name, value, limit, ok)

    def number(self, name, value, limit):
        ok = (value is not None and math.isfinite(value) and value <= limit)
        self.rows.append((name, value, limit, ok))
        log(f"compared {name} = {value!r} limit {limit!r} "
            f"{'ok' if ok else 'FAILED'}")
        return ok

    def fact(self, name, ok, detail=""):
        self.rows.append((name, None, None, bool(ok)))
        log(f"checked {name}: {'ok' if ok else 'FAILED'} {detail}")
        return bool(ok)

    @property
    def correct(self):
        return all(r[3] for r in self.rows)


def dispatch_as_expected(verdict, dispatch, expect):
    """Every op the cell's file names took the path it names, and was
    dispatched at all."""
    for op, path in expect.items():
        seen = dispatch.get(op, {})
        verdict.fact(
            f"dispatch.{op}", bool(seen) and set(seen.values()) == {path},
            f"wanted {path}, saw {seen}")


def worst_leaf_gap(program, reference):
    """The widest gap, over the leaves, between the program's norm and
    the reference's norm of that leaf, against the reference's norm of
    that leaf or of the median leaf, whichever is larger (some gradients
    are all but zero)."""
    program, reference = np.asarray(program), np.asarray(reference)
    floor = np.median(reference)
    gaps = np.abs(program - reference) / np.maximum(reference, floor)
    return float(gaps.max()), int(gaps.argmax())


def train_numbers(facts, cell, seed, precision="fp32", devices=None):
    """The three numbers of a train cell, the plain reference following
    the program's first three updates on the batches it was fed."""
    import jax
    import jax.numpy as jnp

    probe = facts["probe"]
    params = weights.as_dict(weights.make(probe.abstract, seed))
    leaves = lambda t: jax.tree_util.tree_leaves(t)
    norms = lambda t: [float(jnp.sqrt(jnp.sum(jnp.square(x)))) for x in leaves(t)]
    losses, first_grads, after = cell["family"].reference_train_steps(
        params, probe.first_batches, probe.pad, cell["config"], precision,
        devices)
    change = jax.tree_util.tree_map(lambda a, b: a - b, after, params)
    return {"losses": losses, "first_grad_norms": norms(first_grads),
            "param_change_norms": norms(change)}


def compare_train(verdict, program, reference, limits):
    """``program`` and ``reference``: ``{losses, first_grad_norms,
    param_change_norms}``."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(program["losses"], reference["losses"]))
    grad_gap, gi = worst_leaf_gap(program["first_grad_norms"],
                                  reference["first_grad_norms"])
    change_gap, ci = worst_leaf_gap(program["param_change_norms"],
                                    reference["param_change_norms"])
    log(f"losses program {program['losses']} reference {reference['losses']}; "
        f"worst gradient leaf {gi}, worst change leaf {ci}")
    verdict.number("loss_rel_gap", loss_gap, limits["loss_rel_gap"])
    verdict.number("first_grad_leaf_gap", grad_gap,
                   limits["first_grad_leaf_gap"])
    verdict.number("param_change_leaf_gap", change_gap,
                   limits["param_change_leaf_gap"])


def serve_sample(sent, seed, n):
    """``n`` finished in-window requests drawn from the seed, the longest
    among them."""
    done = [tr for tr in sent if tr.in_window and tr.finished_at is not None
            and tr.seq.finish_reason in OK_REASONS
            and tr.seq.generated]
    if not done:
        return []
    longest = max(done, key=lambda tr: len(tr.spec["prompt"])
                  + len(tr.seq.generated))
    rest = [tr for tr in done if tr is not longest]
    rng = np.random.default_rng(seed)
    picks = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in picks]


LOWER = "bf16"  # the precision below a float32 serve cell's


def serve_gaps(params, cell, sample):
    """For every served token of the sample, by how much its logit lies
    below the reference's best at that position; and, at the same
    positions of the same tokens, the same for the token that the
    reference computed one precision down (bfloat16 weights, activations
    and cache) puts first.  Two lists, one entry per served token."""
    import jax
    import jax.numpy as jnp

    cfg, logits = cell["config"], cell["family"].reference_logits
    tree = weights.as_dict(params)
    # sequences padded to a few lengths, so a few programs serve them all
    # (padding at the end of a causal pass changes nothing before it)
    max_pos = cfg["max_position_embeddings"]
    pad_to = max(1, max_pos // 4)
    fwd = jax.jit(lambda p, t, prec: logits(p, t, cfg, prec),
                  static_argnums=(2,))
    served_gaps, lower_gaps = [], []
    for tr in sample:
        prompt, served = tr.spec["prompt"], list(tr.seq.generated)
        seq = prompt + served
        T = min(-(-len(seq) // pad_to) * pad_to, max_pos)
        toks = np.full((T,), 4, np.int32)
        toks[:len(seq)] = seq
        rows = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
        ref = fwd(tree, jnp.asarray(toks), "fp32")[rows]
        best = jnp.max(ref, axis=-1)
        lower = jnp.argmax(fwd(tree, jnp.asarray(toks), LOWER)[rows], axis=-1)
        for picked, out in ((jnp.asarray(served), served_gaps),
                            (lower, lower_gaps)):
            got = jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]
            out.extend(np.asarray(best - got).tolist())
    return served_gaps, lower_gaps


def serve_numbers(served_gaps, lower_gaps):
    """The two numbers of a serve cell.  ``logit_gap_max``: the widest
    gap of a served token (a wrong token, a stale page, a bad mask).
    ``gap_share_of_bf16``: the gaps of the served tokens, summed, over
    the summed gaps of what bfloat16 would have served at the same
    positions.  How many logits lie close enough together to be moved
    differs from seed to seed by an order of magnitude; it moves both
    sums alike, so the share is steady where the sums are not, and a
    program that computes in bfloat16 itself reads 1."""
    served, lower = float(np.sum(served_gaps)), float(np.sum(lower_gaps))
    if lower > 0:
        share = served / lower
    else:
        share = 0.0 if served == 0 else float("inf")
    return {"logit_gap_max": float(max(served_gaps, default=float("inf"))),
            "gap_share_of_bf16": share,
            "tokens": len(served_gaps),
            "moved": sum(1 for g in served_gaps if g > 0),
            "moved_by_bf16": sum(1 for g in lower_gaps if g > 0)}


def compare_serve(verdict, numbers, limits):
    log(f"served tokens compared {numbers['tokens']}, not the reference's "
        f"first choice {numbers['moved']}, bf16's not {numbers['moved_by_bf16']}")
    for name in ("logit_gap_max", "gap_share_of_bf16"):
        verdict.number(name, numbers[name], limits[name])
