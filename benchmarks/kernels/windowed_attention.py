"""Operations and bytes of one paged attention call of a model whose
layers are of two kinds, from the rows it serves: ``rows = [(query
tokens, context length), ...]``, a decode row being ``(1, context)``, and
``window`` the keys a sliding layer's query sees (0: a global layer, every
key up to its own).

THE MODEL'S work, whatever the kernel reads: query ``i`` of a row (at
position ``context - q + i``) has ``min(position + 1, window)`` keys in a
sliding layer; the keys and values a row needs are those some query of it
sees, once (``min(context, window + q - 1)`` in a sliding layer), for the
K/V heads; its queries in and its outputs out for the QUERY heads.  A
kernel that fetches whole pages or blocks, or walks a block only one of
its queries sees, moves and multiplies more, and that shows as a lower
share."""


def _pairs(q, ctx, window):
    """Query-key pairs of one row: token ``i`` of the chunk attends the
    keys up to itself, at most ``window`` of them."""
    if not window:
        return q * ctx - q * (q - 1) / 2.0
    first = ctx - q  # position of the chunk's first query
    return float(sum(min(first + i + 1, window) for i in range(q)))


def visible_keys(q, ctx, window):
    return ctx if not window else min(ctx, window + q - 1)


def flops(rows, heads, head_dim, window=0):
    return sum(2.0 * 2.0 * _pairs(q, ctx, window) * heads * head_dim
               for q, ctx in rows)  # QK^T and PV


def bytes_moved(rows, heads, kv_heads, head_dim, kv_itemsize, act_itemsize,
                window=0):
    total = 0.0
    for q, ctx in rows:
        total += 2.0 * visible_keys(q, ctx, window) * kv_heads * head_dim \
            * kv_itemsize
        total += 2.0 * q * heads * head_dim * act_itemsize
    return total
