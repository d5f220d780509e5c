"""Operations and bytes of one layer's multi-head latent attention over a
step's rows: ``rows = [(query tokens, context length), ...]``, one per
batch row, a decode row being ``(1, context)``.

The SAME work whatever implements it, absorbed or per head: every
(query, key) pair of every head costs a score over ``nope + rope`` numbers
and a weighted sum over ``v`` numbers, two operations each; the bytes are
the context's cache entries (``latent + rope`` numbers a token, ONE vector
for all heads) read once, the queries in (``nope + rope`` a head) and the
outputs out (``v`` a head).  Not the program's: the absorbed form scores
over ``latent + rope`` and sums over ``latent`` numbers a pair (3.4 times
the operations at the published widths) and projects into and out of the
latent space; the per-head form expands the context's keys and values
first; a page read twice, padded lanes and a tile's re-read of its row's
context move more.  All of that shows as a lower share, and none of it can
lift the share over 100%."""


def pairs(q, ctx):
    """(query, key) pairs of a row: token i of the chunk attends to the
    context up to itself."""
    return q * ctx - q * (q - 1) / 2.0


def flops(rows, heads, nope, rope, v):
    return sum(2.0 * pairs(q, ctx) * heads * (nope + rope + v)
               for q, ctx in rows)


def bytes_moved(rows, heads, latent, nope, rope, v, cache_itemsize,
                act_itemsize):
    total = 0.0
    for q, ctx in rows:
        total += ctx * (latent + rope) * cache_itemsize
        total += q * heads * (nope + rope + v) * act_itemsize
    return total
