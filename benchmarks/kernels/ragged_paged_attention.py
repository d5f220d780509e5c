"""Operations and bytes of one ragged paged attention call, from the
rows it serves: ``rows = [(query tokens, context length), ...]``, one
per batch row, a decode row being ``(1, context)``.

The algorithm's bytes: the keys and values a row needs (its context,
once), its queries in and its outputs out.  Not the kernel's: a
page-granular fetch, or a slab that carries more heads than it needs,
moves more, and that shows as a lower share."""


def flops(rows, heads, head_dim):
    total = 0.0
    for q, ctx in rows:
        # token i of the chunk attends to the context up to itself
        pairs = q * ctx - q * (q - 1) / 2.0
        total += 2.0 * 2.0 * pairs * heads * head_dim  # QK^T and PV
    return total


def bytes_moved(rows, heads, head_dim, kv_itemsize, act_itemsize):
    total = 0.0
    for q, ctx in rows:
        total += 2.0 * ctx * heads * head_dim * kv_itemsize
        total += 2.0 * q * heads * head_dim * act_itemsize
    return total
