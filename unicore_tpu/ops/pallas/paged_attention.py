"""Ragged paged-attention kernel (Pallas/TPU): mixed prefill + decode.

One grid program per batch row.  A row carries ``T`` query tokens at
per-token global ``positions`` ([B, T] int32, -1 = inactive padding): a
DECODE row has one real token, a PREFILL-CHUNK row up to ``T`` — both
shapes run in the SAME program, which is what lets the serve engine
dispatch a mixed batch in one compiled step (the "Ragged Paged
Attention" shape, arxiv 2604.15464).  The program walks that row's page
table (scalar-prefetched into SMEM), DMAs each block of
``pages_per_block`` KV pages HBM -> VMEM scratch, and folds them into an
online-softmax accumulator per (head, query) — the gathered
``[B, S, H, D]`` key/value tensor the eager path materializes never
exists, and per-row ``lengths`` make the work RAGGED: a row holding 3
pages stops after 3 DMAs regardless of the table width.

The K/V scratch is TWO slots, and the DMAs of the block after this one
are in flight while this one is multiplied: the next block of the row
or, under the row's last block, block 0 of the NEXT LIVE ROW (the next
row whose length is not 0, found by walking ``lengths`` in SMEM).  Block
``n`` of the call, counted over its live rows, lands in slot ``n % 2``.
Two words of SMEM scratch go from program to program: whether a block 0
is in flight, and the slot the next block lands in.  The online softmax
visits the same blocks in the same order as a kernel that waited for
each, so the arithmetic is the same, operation for operation.  THE
INVARIANT, which every caller's rows must find kept (the latent step
hands over 256 rows of which most are empty, empty ones between live
ones; ``tests/test_serve.py`` ``test_ragged_kernel_row_patterns`` and
``chip_smoke.py``'s kernel phase name each point):

1. every DMA that is started is waited for exactly once, before any read
   of its destination slot, by the program that started it or, for the
   carried block 0, by the next program that has a block;
2. a row of length 0 starts no DMA and waits for none, wherever it lies
   (first, last, between two live rows, many in a row), and touches
   neither word of the SMEM scratch;
3. no DMA writes a slot that a dot not yet issued still reads: the slot
   of block ``n + 1`` is the one block ``n - 1`` was multiplied from,
   and its DMAs start after block ``n - 1``'s dots in program order;
4. the last program leaves no DMA in flight and no semaphore signalled:
   the last live row finds no next live row and starts nothing.

A Pallas interpreter runs a copy where it is started (or where it is
waited for): it shows a wrong slot and, with its race detector, a wrong
order, never a race's timing.  Only the compiled kernel on the chip can.

Layout: the pool is ``[num_slots, H*D]`` — heads folded into the lane
dimension, so a page is a ``[page_size, H*D]`` tile-aligned slab and one
DMA moves it (a ``[.., H, 64]`` pool has a 64-wide minor dim, which the
chip's compiler refuses to DMA and which wastes half of every HBM tile).
Heads are read back as 128-lane SLABS: with ``D == 64`` a slab holds
two heads, and each head's scores are the slab-wide contraction of the
keys with the query masked to that head's lanes (the other head's lanes
contribute exact zeros).  That costs the MXU ``128 // D`` times the
FLOPs of a per-head contraction and needs no unaligned lane slice; the
step is bound by the KV bytes it streams, not by those FLOPs.

Causality is one compare: gathered column ``j`` of a row's view IS
position ``j`` (the pool layout invariant), so column ``c`` is admitted
for query ``t`` iff ``c <= positions[b, t]`` — which also excludes
unwritten/stale slots, since every real query position is below the
row's length.  Inactive query columns (position -1) mask everything and
come out finite (garbage by contract, discarded by the caller).

A WINDOW (``window`` > 0, a sliding layer) admits for the query at
position ``p`` the columns ``p - window < c <= p`` and no older one.  A
row's walk then starts at the block that holds the first column its
EARLIEST live query sees (``first_blocks``, a third scalar-prefetch
operand worked out beside the call) and reads no block wholly behind it;
"block 0" in the invariant above reads "the row's first block", and the
slot a block lands in counts the blocks walked, not their index.  A block
the row's later queries cannot see is still walked when an earlier query
can: the mask decides per query.  With ``window == 0`` the traced program
is the one it was before windows, equation for equation
(``tests/test_serve_window.py``).

The rule for float32 operands is the step's: they multiply in three
bfloat16 passes, as ``Linear`` does outside the kernel at
``Precision.HIGH`` (``three_pass=True``; the latent step asks for it,
and so does the window model's, ``examples/lm/laguna.py``: at one pass a
served token now and then got another of its held experts, PERF.md
section 6, PR 43).  ONE exception is left: the per-head K/V pages of the
three configurations that had them before (``serve/attention.py``
``write_and_attend`` without ``three_pass``) take the one pass the kernel
had before ``Linear`` went to ``HIGH``, because every cell that runs them
was measured and its limits read with it.  Ending the exception is ROADMAP M12's question,
not a caller's choice: no third arithmetic, and no new caller of the
one-pass form.

Dispatch (serve/attention.py) gates on ``use_pallas`` and ``supported``
(a static shape rule).  There is no compile probe: a shape ``supported``
admits and the chip's compiler refuses fails the serve step's compile,
loudly.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from unicore_tpu.ops.backend import pallas_interpret

_LANES = 128
# K/V scratch slots: one is multiplied while the other is filled
SLOTS = 2
# VMEM budget for ONE slot's K and V blocks (what decides the pages a
# block; ``vmem_limit_bytes`` counts both slots and everything else)
_SCRATCH_BUDGET_BYTES = 8 << 20
# the chip's default scoped-VMEM limit, and what the compiler is left for
# its own temporaries (the [T, S] scores, a three-pass dot's split
# operands) above the buffers ``vmem_limit_bytes`` counts
_DEFAULT_SCOPED_VMEM_BYTES = 16 << 20
_COMPILER_ROOM_BYTES = 6 << 20
# the two words of SMEM scratch one program hands the next
_IN_FLIGHT, _NEXT_SLOT = 0, 1


def slab_heads(heads, head_dim):
    """Heads per lane slab: as many as fit 128 lanes (1 when a head
    fills a slab on its own), kept a divisor of the head count."""
    g = max(1, min(heads, _LANES // head_dim))
    while heads % g:
        g -= 1
    return g


def supported(heads, head_dim, page_size, itemsize):
    """Static shape rule for the COMPILED kernel (interpret mode takes
    any shape): slabs are whole 128-lane tiles and a page is whole
    sublane tiles of the pool dtype (8 rows of 32 bits)."""
    slab = slab_heads(heads, head_dim) * head_dim
    return slab % _LANES == 0 and page_size % (8 * 4 // itemsize) == 0


def pick_pages_per_block(num_table_pages, page_size, head_dim,
                         num_heads=8, itemsize=2):
    """Pages DMA'd per online-softmax block: ~256 gathered slots per
    block — enough rows to amortize the DMA issue latency — with one
    slot's K and V held inside the scratch budget.  The second slot does
    not shrink a block (``vmem_limit_bytes`` pays for it instead), so
    every shape walks the blocks it walked with one slot.  The 256 has
    not been swept on this machine."""
    def fits(pp):
        return (2 * pp * page_size * num_heads * head_dim * itemsize
                <= _SCRATCH_BUDGET_BYTES)

    pp = max(1, min(int(num_table_pages), -(-256 // int(page_size))))
    while pp > 1 and not fits(pp):
        pp -= 1
    return pp


def vmem_limit_bytes(cells, lanes, heads, blk_slots, kv_itemsize,
                     q_itemsize, three_pass_slabs=0):
    """Scoped VMEM one program may use, from its shapes alone: both
    slots' K and V blocks, the queries and the outputs (each double-
    buffered by the pipeline), the accumulator, the running max and sum
    (a ``[cells, 1]`` column a head fills whole 128-lane tiles), and
    ``_COMPILER_ROOM_BYTES``; never under the chip's default of 16 MB.
    ``opt_1.3b``'s mixed program (128 cells x 2,048 lanes, blocks of 256
    float32 slots) comes to 23 MB, the hybrid's (64 x 3,840) to 27.6 MB,
    of the 128 MB of VMEM a v5e has.  ``three_pass_slabs``: the lane
    slabs of a call that multiplies in three passes; the loop over slabs
    is unrolled and the compiler keeps the split operands and the three
    ``[cells, block]`` partial scores of more than one slab alive, two
    score tiles a slab beyond the first (the window model's mixed
    program, 1,024 cells x 8 slabs, needed 48.6 MB where the count
    without them gave 38: compiled for a described v5e, PR 43; the latent
    step has one slab and asks for nothing more)."""
    room = max(0, three_pass_slabs - 1) * 2 * cells * blk_slots * 4
    kv = SLOTS * 2 * blk_slots * lanes * kv_itemsize
    piped = 2 * 2 * cells * lanes * q_itemsize
    acc = cells * lanes * 4
    stats = 2 * heads * (-(-cells // 8) * 8) * _LANES * 4
    return max(_DEFAULT_SCOPED_VMEM_BYTES,
               kv + piped + acc + stats + _COMPILER_ROOM_BYTES + room)


def _dot(a, b, contract, three_pass):
    """``a`` contracted with ``b`` over ``contract``, float32 out.  As the
    kernel always had it, float32 operands take ONE bfloat16 pass (both
    rounded to 8 bits of mantissa).  ``three_pass``: each float32 operand
    is split into a bfloat16 head and a bfloat16 remainder and the three
    products that matter are summed (``hi x hi + hi x lo + lo x hi``:
    2^-16 of a product, what XLA's ``Precision.HIGH`` does), three times
    the MXU's work."""
    dot = lambda x, y: jax.lax.dot_general(  # noqa: E731
        x, y, ((contract), ((), ())), preferred_element_type=jnp.float32)
    if not three_pass:
        return dot(a, b)

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)


def _kernel(pt_ref, len_ref, *refs, page_size, pages_per_block, scale,
            heads, head_dim, three_pass, window=0):
    # a windowed call brings each row's first block as a third prefetched
    # scalar operand; without a window every row starts at block 0
    first_ref, refs = (refs[0], refs[1:]) if window else (None, refs)
    (pos_ref, q_ref, kp_hbm, vp_hbm, o_ref,
     k_scr, v_scr, m_scr, l_scr, acc_scr, sems, carry) = refs
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    length = len_ref[b]
    n_table = pt_ref.shape[1]
    blk_slots = pages_per_block * page_size
    n_blocks = pl.cdiv(length, blk_slots)
    # the blocks ``first .. n_blocks - 1`` are walked (a live row has one:
    # ``first_blocks`` holds ``first`` under the row's last block)
    first = first_ref[b] if window else 0
    group = slab_heads(heads, head_dim)
    slab = group * head_dim
    t = q_ref.shape[1]

    # query positions [T, 1]: -1 marks an inactive column (mask all)
    pos_q = pos_ref[0]
    # which head of its slab each lane belongs to
    lane_head = jax.lax.broadcasted_iota(
        jnp.int32, (1, slab), 1) // head_dim
    m_scr[...] = jnp.full(m_scr.shape, -1e30, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # what one program hands the next (SMEM scratch outlives a program):
    # whether block 0 of the next live row is in flight, and the slot the
    # next block lands in
    @pl.when(b == 0)
    def _():
        carry[_IN_FLIGHT] = 0
        carry[_NEXT_SLOT] = 0

    def copies(row, blk, slot):
        """The DMAs of block ``blk`` of row ``row`` into ``slot``: the
        same descriptors start them and wait for them.  Table rows are
        padded with the trash page 0, so a clamped out-of-range read
        fetches page 0 — always a valid pool page, masked below."""
        out = []
        for j in range(pages_per_block):
            page = pt_ref[row, jnp.minimum(blk * pages_per_block + j,
                                           n_table - 1)]
            rows = pl.ds(j * page_size, page_size)
            for src, dst, s in ((kp_hbm, k_scr, 0), (vp_hbm, v_scr, 1)):
                out.append(pltpu.make_async_copy(
                    src.at[page], dst.at[slot, rows], sems.at[slot, s, j]))
        return out

    # the next row that has a block (``n_rows``: none): rows of length 0
    # are stepped over here and do nothing below, so they neither start
    # nor wait (invariant 2); an empty row does not search either (the
    # latent step's trailing empty tiles would each walk to the end)
    nxt = jax.lax.while_loop(
        lambda r: jnp.logical_and(
            r < n_rows, len_ref[jnp.minimum(r, n_rows - 1)] == 0),
        lambda r: r + 1, jnp.where(n_blocks > 0, b + 1, n_rows))
    has_next = nxt < n_rows
    slot0 = carry[_NEXT_SLOT]

    # the first live row of the call: nobody fetched its first block
    @pl.when(jnp.logical_and(n_blocks > 0, carry[_IN_FLIGHT] == 0))
    def _():
        for cp in copies(b, first, slot0):
            cp.start()

    def body(i, loop_carry):
        slot = (slot0 + (i - first if window else i)) % SLOTS
        # the block after this one goes into the other slot while this
        # one is multiplied: this row's next block or, under its last,
        # block 0 of the next live row.  The other slot was read by the
        # block before this one, whose dots are issued (invariant 3)
        in_row = i + 1 < n_blocks

        @pl.when(jnp.logical_or(in_row, has_next))
        def _():
            row = jnp.where(in_row, b, jnp.minimum(nxt, n_rows - 1))
            for cp in copies(row, jnp.where(
                    in_row, i + 1, first_ref[row] if window else 0),
                    1 - slot):
                cp.start()

        # started once (by the block before, or above), waited once, here,
        # before the first read of the slot (invariant 1)
        for cp in copies(b, i, slot):
            cp.wait()
        cols = i * blk_slots + jax.lax.broadcasted_iota(
            jnp.int32, (1, blk_slots), 1
        )
        # bottom-right causal + unwritten-slot exclusion in one compare
        # (every real query position is < length by construction)
        valid = cols <= pos_q  # [T, S]
        if window:
            # and nothing older than the query's window
            valid = jnp.logical_and(valid, cols > pos_q - window)
        for sl in range(heads // group):
            lanes = pl.ds(sl * slab, slab)
            q = q_ref[0, :, lanes] * scale  # [T, slab]
            k = k_scr[slot, :, lanes]       # [S, slab]
            v = v_scr[slot, :, lanes]
            acc = acc_scr[:, lanes]
            for g in range(group):
                h = sl * group + g
                mine = lane_head == g
                qh = q if group == 1 else jnp.where(
                    mine, q, jnp.zeros_like(q))
                s = _dot(qh, k, ((1,), (1,)), three_pass)  # [T, S]
                s = jnp.where(valid, s, -1e30)
                m = m_scr[h]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                # a query whose positions precede this whole block has
                # m_new == -1e30 == s; exp(0) would admit every masked
                # column, so the probability is zeroed explicitly
                # rather than through the subtraction
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(m - m_new)
                l_scr[h] = l_scr[h] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True)
                m_scr[h] = m_new
                pv = _dot(p.astype(v.dtype), v, ((1,), (0,)),
                          three_pass)  # [T, slab]
                # only this head's lanes of pv are its p @ v
                acc = jnp.where(mine, acc * alpha + pv, acc)
            acc_scr[:, lanes] = acc
        return loop_carry

    jax.lax.fori_loop(first, n_blocks, body, 0)

    # a live row hands on what its last block started; the last live row
    # started nothing, so the call ends with no DMA in flight (invariant 4)
    @pl.when(n_blocks > 0)
    def _():
        carry[_IN_FLIGHT] = has_next.astype(jnp.int32)
        carry[_NEXT_SLOT] = (
            slot0 + (n_blocks - first if window else n_blocks)) % SLOTS

    for sl in range(heads // group):
        lanes = pl.ds(sl * slab, slab)
        denom = jnp.zeros((t, slab), jnp.float32)
        for g in range(group):
            denom = jnp.where(lane_head == g, l_scr[sl * group + g], denom)
        # inactive rows/columns never accumulate; keep them finite
        # instead of 0/0
        o_ref[0, :, lanes] = (
            acc_scr[:, lanes] / jnp.maximum(denom, 1e-30)
        ).astype(o_ref.dtype)


# One trace and one lowering per signature, shared by every layer of a
# step program.  ``pl.pallas_call`` traces the kernel's Python body
# (~600 equations) and lowers it to a kernel module EVERY time it is
# called, and a 24-layer decoder calls it 24 times per compiled width
# with identical shapes and parameters: on the chip's host that was
# ~45 s a width, two thirds of a serve process's set-up.  Under this
# inner ``jit`` the first layer traces and the others hit jit's trace
# cache; the enclosing step lowers ONE private function holding one
# ``tpu_custom_call`` and a call to it per layer, which XLA inlines
# before it schedules anything, so the compiled step holds the same
# kernels in the same order.  The key is jit's own: operand shapes and
# dtypes and the static keywords, so another geometry and another
# ``pages_per_block`` get entries of their own (a static argument
# must hash: ``scale`` arrives as a Python float).  ``interpret`` is
# read by the caller and is part of the key, not read in here once per
# cached trace: it is constant in a process (``backend._on_tpu()``),
# but ``tests/test_chip_compile.py`` steers it in process and must not
# be served a trace taken under the other setting.  Nested under a
# step's trace the inner jit keeps no executable, so
# ``_call._cache_size()`` counts eager calls only.
@functools.partial(
    jax.jit,
    static_argnames=("page_size", "pages_per_block", "scale", "heads",
                     "head_dim", "interpret", "three_pass", "window"),
)
def _call(q3, k_pages3, v_pages3, page_table, lengths, positions, *,
          page_size, pages_per_block, scale, heads, head_dim, interpret,
          three_pass=False, window=0):
    bsz, t, hd = q3.shape
    blk_slots = pages_per_block * page_size
    # scalar-prefetch operands: tables and lengths and, under a window,
    # each row's first block
    scalars = [page_table.astype(jnp.int32), lengths.astype(jnp.int32)]
    kernel = functools.partial(
        _kernel, page_size=page_size, pages_per_block=pages_per_block,
        scale=scale, heads=heads, head_dim=head_dim,
        three_pass=three_pass,
    )
    if window:
        scalars.append(first_blocks(positions, lengths, window, blk_slots))
        kernel = functools.partial(kernel, window=window)
    qo_spec = pl.BlockSpec((1, t, hd), lambda b, *_: (b, 0, 0))
    # [B, T, 1]: the block's last two dims are the array's own, which
    # the TPU lowering takes at any T (a (1, T) block of [B, T] is not)
    pos_spec = pl.BlockSpec((1, t, 1), lambda b, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(bsz,),
        in_specs=[
            pos_spec,
            qo_spec,
            pl.BlockSpec(memory_space=pl.ANY),  # k pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((SLOTS, blk_slots, hd), k_pages3.dtype),
            pltpu.VMEM((SLOTS, blk_slots, hd), v_pages3.dtype),
            pltpu.VMEM((heads, t, 1), jnp.float32),   # running max
            pltpu.VMEM((heads, t, 1), jnp.float32),   # running sum
            pltpu.VMEM((t, hd), jnp.float32),         # accumulator
            pltpu.SemaphoreType.DMA((SLOTS, 2, pages_per_block)),
            pltpu.SMEM((2,), jnp.int32),              # program to program
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, t, hd), q3.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
        compiler_params=pltpu.CompilerParams(
            # programs run in row order on one core: a row's last block
            # starts the next live row's first, and SMEM carries the slot
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes(
                t, hd, heads, blk_slots, k_pages3.dtype.itemsize,
                q3.dtype.itemsize,
                heads // slab_heads(heads, head_dim) if three_pass else 0),
        ),
    )(*scalars, positions.astype(jnp.int32)[:, :, None], q3, k_pages3,
      v_pages3)


def first_blocks(positions, lengths, window, blk_slots):
    """``[B]`` int32: the block each row's walk starts at under
    ``window``, the one that holds the first column its earliest live
    query sees (``min position - window + 1``), never past the row's last
    block, 0 for a row with no live query."""
    positions = positions.astype(jnp.int32)
    live = positions >= 0
    earliest = jnp.min(jnp.where(live, positions, jnp.iinfo(jnp.int32).max),
                       axis=1)
    column = jnp.where(jnp.any(live, axis=1),
                       jnp.maximum(earliest - (window - 1), 0), 0)
    last = jnp.maximum(-(-lengths.astype(jnp.int32) // blk_slots) - 1, 0)
    return jnp.minimum(column // blk_slots, last)


def ragged_paged_attention(q, k_pages, v_pages, page_table, positions,
                           lengths, *, page_size, scale,
                           pages_per_block=None, three_pass=False,
                           window=0):
    """Mixed prefill+decode paged attention: q [B, T, H, D], flat pools
    [num_slots, H*D], page_table [B, P] (pad rows with page 0),
    positions [B, T] per-token global positions (-1 = inactive),
    lengths [B] valid token count incl. this step's (0 = inactive row).
    Returns [B, T, H, D].  ``three_pass``: float32 queries against a
    float32 pool multiply in three bfloat16 passes (``_dot``; the module
    docstring says who asks); every other operand type takes one.
    ``window`` > 0: the query at position ``p`` sees the columns ``p -
    window < c <= p`` only (module docstring)."""
    bsz, t, heads, d = q.shape
    num_pages = k_pages.shape[0] // page_size
    if pages_per_block is None:
        pages_per_block = pick_pages_per_block(
            page_table.shape[1], page_size, d, num_heads=heads,
            itemsize=k_pages.dtype.itemsize,
        )
    out = _call(
        q.reshape(bsz, t, heads * d),
        k_pages.reshape(num_pages, page_size, heads * d),
        v_pages.reshape(num_pages, page_size, heads * d),
        page_table, lengths, positions,
        page_size=page_size, pages_per_block=pages_per_block,
        scale=float(scale), heads=heads, head_dim=d,
        interpret=pallas_interpret(),
        three_pass=bool(three_pass and q.dtype == jnp.float32
                        and k_pages.dtype == jnp.float32),
        # no keyword where there is no window: the call, and so its
        # trace, is the one it was
        **({"window": int(window)} if window else {}),
    )
    return out.reshape(bsz, t, heads, d)


def ragged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                            page_size, scale, pages_per_block=None):
    """Decode-step convenience wrapper (T == 1): each row's single
    query sits at its last valid position."""
    assert q.shape[1] == 1, "use ragged_paged_attention for T > 1"
    positions = (lengths - 1)[:, None].astype(jnp.int32)
    return ragged_paged_attention(
        q, k_pages, v_pages, page_table, positions, lengths,
        page_size=page_size, scale=scale, pages_per_block=pages_per_block,
    )
