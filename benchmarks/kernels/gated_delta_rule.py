"""Operations and bytes of one gated-delta-rule call, from the rows it
serves: ``rows = [(tokens, context length), ...]``, one per batch row (a
decode row is ``(1, context)``; the context does not enter: the state has
one size whatever it has seen).

The algorithm's bytes: per row the state ``[heads, dk, dv]`` read once
and written once, and per token ``q``, ``k`` in, ``v`` in, ``o`` out.
The algorithm's operations, per token and head: decaying the state
(``dk dv``), reading it along ``k``, the rank-one update, reading it
along ``q`` (``2 dk dv`` each).  Not the program's: its chunked form
does more arithmetic to do it in matmuls, and padded columns and empty
rows move and multiply too; that shows as a lower share."""


def flops(rows, heads, key_dim, value_dim):
    tokens = sum(q for q, _ in rows)
    return 7.0 * tokens * heads * key_dim * value_dim


def bytes_moved(rows, heads, key_dim, value_dim, state_itemsize,
                act_itemsize):
    total = 0.0
    for q, _ in rows:
        total += 2.0 * heads * key_dim * value_dim * state_itemsize
        total += q * heads * 2.0 * (key_dim + value_dim) * act_itemsize
    return total
