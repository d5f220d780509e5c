"""Operations and bytes of flash attention, forward and backward, from
its shapes.  The algorithm's counts, not the kernel's: what any
implementation of exact attention with a trainable additive bias must do
once.

Forward: S = QK^T and O = PV, two matmuls of B*H*Tq*Tk*D multiply-adds.
Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q and the
recomputation of S that the flash formulation makes in place of storing
P: five matmuls, the usual 2.5x of the forward.  Key padding is counted
as dense (the masked columns are still multiplied)."""


def flops(batch, heads, tq, tk, head_dim, *, backward, causal=False):
    macs = batch * heads * tq * tk * head_dim
    if causal:
        macs /= 2
    return 2.0 * macs * (5 if backward else 2)


def bytes_moved(batch, heads, tq, tk, head_dim, itemsize, *, backward,
                bias_itemsize=0):
    """q, k, v read and o written once (backward: q, k, v, o, dO read,
    dq, dk, dv written), the row statistics in float32, and a
    batch-broadcast bias ``[H, Tq, Tk]`` read once (backward: its
    gradient written once, in float32)."""
    q = batch * heads * tq * head_dim * itemsize
    kv = batch * heads * tk * head_dim * itemsize
    stats = batch * heads * tq * 4
    bias = heads * tq * tk * bias_itemsize
    if not backward:
        return 2 * q + 2 * kv + stats + bias
    dbias = heads * tq * tk * 4 if bias_itemsize else 0
    return 4 * q + 4 * kv + 2 * stats + bias + dbias
