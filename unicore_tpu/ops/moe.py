"""Sparse experts: the router's choice and the expert feed-forward over
the tokens a step carries.

A layer of ``E`` experts gives every token ``top_k`` of them::

    s   = sigmoid(W_g x)                      E scores, float32
    sel = top_k(s + b)                        b: a bias per expert that
                                              enters the SELECTION only
    w   = s[sel] / (sum s[sel] + eps)         renormalised, times a scale
                                              (eps: the model's, 1e-6
                                              where it gives none)
    y   = sum_{e in sel} w_e W2_e(silu(W1_e x) * W3_e x)

:func:`route` is the first three lines, :func:`expert_ffn` the last.  No
token is dropped and no expert has a capacity: what a token is owed it
gets, whatever the load.

**What it costs.**  The step's tokens are a flat list (``serve/engine.py``)
of which most cells may be empty; a cell nobody carries (``valid`` false)
reaches no expert and is counted nowhere.  The ``tokens x top_k``
assignments are put in expert order by a counting sort (a one-hot, its
running sum: no comparison sort, and ties keep the tokens' order), each
expert's group padded to whole BLOCKS of ``block_rows`` rows, and one
loop runs over the blocks that hold a row, each block three matmuls
against ONE expert's weights, sliced out of the stacked ``[E, ...]``
arrays in place.  So the weights read are those of the experts that got
a token (once per block), and the FLOPs those of the routed tokens
rounded up to blocks: not ``tokens x E``.  ``jax.lax.ragged_dot`` states
the same thing in one line, but this jaxlib's TPU compiler expands it
into a dense product of every row with every expert, masked (51.5 GFLOP
for 128 rows of ``[2048] x [64, 2048, 1536]``, compiled for a described
v5e) and on the chip took 3.99 ms against this loop's 3.11 for a decode
step's 32 rows and 18-20 ms against 3.9-4.4 for a mixed step's 512
(PERF.md, PR 31), so it is not used.

**Shares.**  A layer is told which experts it holds (``first_expert``,
and as many as its stacked weights have): the router runs over all ``E``
and the layer computes its own experts' part of ``y``.  The parts of all
the shares add up to the whole layer; on one chip that holds them all
there is one share and no exchange.  A share pays for ITS assignments:
the sort, the blocks (sized from the ``Eh / E`` of the choices a share
expects, ``num_experts``) and the loop run over the held experts only,
and a step in which no held expert got a token runs no block.  What the
absent experts would add is added by nobody here.

Plain XLA on every backend (``dispatch_report()`` says ``reference``).
Float32 operands multiply as ``modules/pattern_decoder.py`` ``Linear``
does (``Precision.HIGH``); the caller computes the router's scores at
``highest``: a score decides WHICH expert runs, and two programs that
round it differently serve different functions of the same weights.
"""

import collections

import jax
import jax.numpy as jnp

from .backend import note_dispatch

SUBLANES = 8      # rows of one float32 tile: the least a block can be
MAX_BLOCK_ROWS = 128


def route(scores, bias, top_k, scale=1.0, eps=1e-6):
    """``scores`` [N, E] float32 (already through the sigmoid), ``bias``
    [E] or None (the scores alone choose).  Returns ``(sel [N, top_k]
    int32, w [N, top_k] float32)``: the experts chosen by ``scores +
    bias`` (ties as ``jax.lax.top_k`` breaks them: the lower index first)
    and their weights from ``scores`` alone, renormalised over the chosen
    with ``eps`` under the division."""
    with jax.named_scope("moe_router"):
        scores = scores.astype(jnp.float32)
        chosen_by = scores if bias is None else scores + bias.astype(
            jnp.float32)
        _, sel = jax.lax.top_k(chosen_by, top_k)
        w = jnp.take_along_axis(scores, sel, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
        return sel.astype(jnp.int32), w * scale


def pick_block_rows(assignments, experts_held):
    """Rows of one block, from shape: TWICE the mean group, rounded up to
    whole sublane tiles, held between one tile and ``MAX_BLOCK_ROWS``.  A
    group that needs a second block reads its expert's weights a second
    time, and a block of three-pass float32 matmuls stays weight-bound on
    a v5e up to ~128 rows, so padding is cheap and a second block is not:
    a full mixed list (512 tokens x 4 over 64 experts, the hottest expert
    at three times the mean) took 5.77 / 4.37 / 4.67 ms a layer in blocks
    of 32 / 64 / 128, a third-full one 3.94 / 3.96 / 4.47 (7.01 at 8), a
    decode step of 32 rows 3.11 / 3.12 / 3.13 at 8 / 16 / 32 (PERF.md, PR
    31).  A decode step of 32 rows x 4 over 64 experts: 8; a mixed step
    of 512 tokens: 64.  ``assignments`` are those the held experts can
    expect: a share of 8 of 256 experts under 8 a token gets 128 of a
    512-token list's 4,096 (blocks of 32) and 4 of a 16-row decode
    step's 128 (blocks of 8)."""
    twice = 2 * -(-int(assignments) // max(1, int(experts_held)))
    return max(SUBLANES,
               min(MAX_BLOCK_ROWS, -(-twice // SUBLANES) * SUBLANES))


def _dot(a, b):
    dtype = jnp.result_type(a.dtype, b.dtype)
    precision = jax.lax.Precision.HIGH if dtype == jnp.float32 else None
    return jnp.dot(a.astype(dtype), b.astype(dtype), precision=precision)


def expert_ffn(x, valid, w1, w3, w2, sel, w, first_expert=0,
               block_rows=None, num_experts=None):
    """``x`` [N, D] tokens, ``valid`` [N] bool (or None: all), ``w1`` /
    ``w3`` [Eh, D, F] and ``w2`` [Eh, F, D] the stacked weights of the
    ``Eh`` experts held here, which are experts ``first_expert ..
    first_expert + Eh - 1`` of the layer's ``num_experts`` (None: ``Eh``,
    all of them); ``sel`` / ``w`` [N, top_k] from :func:`route`.  Returns
    ``(y [N, D], load [Eh] int32)``: this share's part of the layer's
    output (zero for a token that is not valid or chose no expert held
    here) and how many valid tokens each held expert got."""
    N, D = x.shape
    Eh = w1.shape[0]
    k = sel.shape[1]
    A = N * k
    # the choices a share can expect: Eh / num_experts of them
    bm = int(block_rows or pick_block_rows(
        -(-A * Eh // int(num_experts or Eh)), Eh))
    # the most blocks any load can need (every choice of every token may
    # still land here, no token is dropped): every group's last block ragged
    NB = -(-(A + Eh * (bm - 1)) // bm)
    R = NB * bm
    note_dispatch("moe_experts",
                  "n%d k%d e%d d%d f%d blk%d %s" % (
                      N, k, Eh, D, w1.shape[2], bm, x.dtype.name), False)
    with jax.named_scope("moe_experts"):
        local = sel - first_expert
        mine = (local >= 0) & (local < Eh)
        if valid is not None:
            mine = mine & valid[:, None]
        mine = mine.reshape(A)
        gid = jnp.where(mine, local.reshape(A), Eh)   # Eh: nobody's
        onehot = gid[:, None] == jnp.arange(Eh, dtype=gid.dtype)[None]
        running = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
        load = running[-1]                                    # [Eh]
        rank = jnp.sum(jnp.where(onehot, running - 1, 0), axis=1)
        padded = -(-load // bm) * bm
        ends = jnp.cumsum(padded)
        starts = ends - padded
        # row of the padded, expert-ordered buffer each assignment takes
        # (R, out of range, for one that is nobody's: dropped / clipped)
        dest = jnp.where(
            mine, jnp.take(starts, gid, mode="clip") + rank, R)
        row_token = jnp.zeros((R,), jnp.int32).at[dest].set(
            jnp.arange(A, dtype=jnp.int32) // k, mode="drop")
        # rows in expert order, by blocks: [NB, bm, D] in and out of the
        # loop (the result type the benchmark's readers know the third
        # matmul by: benchmarks/lib/moe_readers.py)
        xs = jnp.take(x, row_token, axis=0, mode="clip").reshape(NB, bm, D)
        blocks_used = ends[-1] // bm
        block_expert = jnp.sum(
            (jnp.arange(NB, dtype=jnp.int32) * bm)[:, None] >= ends[None],
            axis=1).astype(jnp.int32)

        def one_block(b, ys):
            e = jnp.minimum(block_expert[b], Eh - 1)
            pick = lambda t, i: jax.lax.dynamic_index_in_dim(
                t, i, axis=0, keepdims=False)
            xb = pick(xs, b)
            hidden = jax.nn.silu(_dot(xb, pick(w1, e))) * _dot(xb, pick(w3, e))
            return jax.lax.dynamic_update_index_in_dim(
                ys, _dot(hidden, pick(w2, e)).astype(ys.dtype), b, axis=0)

        ys = jax.lax.fori_loop(
            0, blocks_used, one_block,
            jnp.zeros((NB, bm, D), jnp.result_type(x.dtype, w2.dtype)))
        out = jnp.take(ys.reshape(R, D), dest, axis=0, mode="clip")  # [A, D]
        out = jnp.where(mine[:, None], out, 0) * w.reshape(A, 1).astype(
            out.dtype)
        return out.reshape(N, k, D).sum(axis=1).astype(x.dtype), load


# What the routers of this process did, a serve step at a time, as
# ``backend.dispatch_report()`` is the account of the kernels: the serve
# engine notes each step's sums over its expert layers (they come back
# with the step's tokens), and a report or a benchmark's reader takes a
# copy.  Totals are the engine's (``ServeEngine.stats``).
_ROUTING = collections.deque(maxlen=4096)


def note_routing(assignments, experts_touched):
    _ROUTING.append((assignments, experts_touched))


def routing_report():
    """The last 4,096 serve steps of this process, oldest first, as
    ``(assignments, experts_touched)``: token x expert pairs and experts
    that got a token, each summed over the step's expert layers.  Of an
    engine whose layers hold a SHARE of their experts: the pairs that
    landed on held experts and the held experts that got a token, which
    is the work its expert loops did."""
    return list(_ROUTING)
