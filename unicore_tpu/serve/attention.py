"""Paged attention over page tables: the serve tier's attention core.

Layouts: the attention core takes queries in the module convention
``[B, T, H, D]`` (a serve step whose tokens are a flat list sorts them
into that rectangle here, in ``write_and_attend``); the pool
is FLAT — ``k_pages``/``v_pages`` are ``[num_slots, H*D]`` (``H`` the K/V
heads: fewer than the query heads under grouped queries, see
``write_and_attend``; heads folded
into the minor dim, so a page is a tile-aligned slab the kernel can DMA
and no HBM tile is half empty at ``D == 64``) where slot
``page * page_size + offset`` holds the token at ``position`` such that
``page == position // page_size`` in that sequence's table.  Gathering a
sequence's pages in table order therefore reproduces its keys in
position order, and causal masking is a plain compare of gathered column
index against the query's position.

Two implementations:

- the **eager gather path** (``paged_attention_reference``) — a fused
  take + einsum + fp32 softmax composition.  It is the semantics oracle,
  runs everywhere (CPU tier-1), and is what XLA fuses well at small
  batch.
- an optional **Pallas ragged kernel**
  (``ops/pallas/paged_attention.py``) for the serve engine's unified
  step on TPU: one grid program per batch row — a row carries either a
  single decode token or a prefill chunk, both in the SAME program —
  DMAs that row's pages HBM -> VMEM and accumulates an online softmax
  per (head, query); the gathered ``[B, S, H, D]`` key tensor never
  materializes.  Gated through ``ops/backend.py`` (``use_pallas``)
  and the kernel's static shape rule (``supported``); its page block is
  ``pick_pages_per_block``'s, from shape.  The path each compiled width
  took is in ``backend.dispatch_report()``, with the pages a block and
  the K/V slots of a shape that took the kernel; a kernel the chip's
  compiler refuses fails the step's compile.
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class PagedMeta:
    """Per-step paged-cache operands, built INSIDE the jitted step (this
    is not a pytree; only its array fields are traced).

    ``page_table`` [B, P] int32 (rows padded with the trash page 0);
    ``slot_mapping`` [N] int32 flat write slots for the step's tokens
    (the trash slot for a token nobody carries); ``lengths`` [B] int32
    valid token counts INCLUDING the current tokens;
    ``page_size``/``num_slots`` are static Python ints (``num_slots``
    sizes the pool variables at flax init and is ignored afterwards).

    The step's tokens may come as the ``[B, width]`` rectangle itself
    (N = B * width, row-major: the decode step, where token b is row b)
    or as a FLAT list of N <= B * width tokens, each row's tokens
    consecutive.  The flat list brings its map both ways, for the mixers
    that need rows (the ragged kernel here, a recurrent layer's chain in
    ``modules/pattern_decoder.py``):
    ``rect_token`` [B, width] int32, the flat token in each cell of the
    rectangle (N for a cell no token fills), ``rect_positions``
    [B, width] int32, that token's position (-1 for such a cell), and
    ``token_cell`` [N] int32, the cell ``row * width + column`` of each
    flat token.  ``last_token`` [B] int32, where given, names the flat
    token each row samples from: the model then returns logits for
    those B tokens only.

    A model with recurrent layers also gets ``state_slots`` [B] int32
    (the layout of its tokens is the one above, like any other model's):
    the state-store slot of each row's SEQUENCE (a row is assigned anew
    every step, the state is not), out of range for an empty row so that
    its write is dropped; ``num_state_slots`` sizes the store at init.

    A model with SLIDING-WINDOW layers gets a second kind of page for
    them (``serve/kv_pool.py``), addressed by a table of its own:
    ``window_page_table`` [B, Pw] int32 holds, for each row, the window
    pages from the one with the first key the row's first query sees to
    the one its last token is written to, padded with the window pool's
    trash page 0; ``window_base`` [B] int32 is the position of the first
    slot of that table's first page (a multiple of the page size), so
    column ``c`` of the row's window view is position ``window_base +
    c``; ``window_slot_mapping`` [N] int32 the tokens' write slots in the
    window pool; ``num_window_slots`` sizes the window layers' pool
    variables at init.  :meth:`windowed` hands a window layer the same
    meta with these in the places of ``page_table`` / ``slot_mapping`` and
    positions counted from ``window_base`` (:meth:`window_positions`): the trimmed table keeps
    "column j is position j" (in the row's own frame), so the kernel's
    masks are compares as they always were.  None for a model without a
    window: its operands are what they were."""

    page_table: Any
    slot_mapping: Any
    lengths: Any
    page_size: int
    num_slots: int = 0
    state_slots: Any = None
    num_state_slots: int = 0
    rect_token: Any = None
    rect_positions: Any = None
    token_cell: Any = None
    last_token: Any = None
    window_page_table: Any = None
    window_slot_mapping: Any = None
    window_base: Any = None
    num_window_slots: int = 0

    # the two mixers that need rows (attention's kernel, a recurrent
    # layer's chain) go through these three; everything else runs on the
    # tokens as they come

    def to_rows(self, x):
        """``x`` [*lead, ...] over the step's tokens (``lead`` two
        dimensions: [1, N] or the rectangle's own) in the ``[B, width,
        ...]`` rectangle: a gather by ``rect_token`` for a flat list (a
        cell no token fills reads some token's value: ``row_positions``
        says -1 there), a reshape when the tokens are the rectangle."""
        if self.rect_token is None:
            return x.reshape((self.page_table.shape[0], -1) + x.shape[2:])
        return jnp.take(x.reshape((-1,) + x.shape[2:]), self.rect_token,
                        axis=0, mode="clip")

    def row_positions(self, positions):
        """The tokens' ``positions`` in the rectangle, -1 for a cell no
        token fills."""
        if self.rect_token is None:
            return positions.reshape(self.page_table.shape[0], -1)
        return self.rect_positions

    def windowed(self):
        """The meta as a sliding-window layer reads it: the window table
        and write slots in the places of the global ones, lengths counted
        from each row's ``window_base`` (0 stays 0)."""
        return dataclasses.replace(
            self, page_table=self.window_page_table,
            slot_mapping=self.window_slot_mapping,
            lengths=jnp.maximum(self.lengths - self.window_base, 0))

    def window_positions(self, row_positions):
        """``row_positions`` [B, width] counted from each row's
        ``window_base`` (-1 stays -1)."""
        return jnp.where(row_positions >= 0,
                         row_positions - self.window_base[:, None], -1)

    def to_tokens(self, x, lead):
        """The way back: ``x`` [B, width, ...] in the tokens' own layout
        ``[*lead, ...]``."""
        feat = x.shape[2:]
        if self.rect_token is not None:
            x = jnp.take(x.reshape((-1,) + feat), self.token_cell, axis=0,
                         mode="clip")
        return x.reshape(lead + feat)


def gather_slots(pages, page_table, page_size):
    """[num_slots, H*D] pool + [B, P] tables -> [B, P*page_size, H*D]
    position-ordered per-sequence views (XLA lowers this to one gather)."""
    bsz, npages = page_table.shape
    flat = (page_table[:, :, None] * page_size
            + jnp.arange(page_size, dtype=page_table.dtype)[None, None, :])
    return pages[flat.reshape(bsz, npages * page_size)]


def paged_attention_reference(q, k_pages, v_pages, page_table, positions,
                              lengths, page_size, scale, window=0):
    """Eager gather-based paged attention (the oracle; CPU tier-1 path).

    ``positions`` [B, T]: global position of each query row (-1 =
    inactive row -> fully masked; output rows for those are garbage by
    contract and discarded by the caller).  ``window`` > 0: a query sees
    the ``window`` columns up to its own and nothing older."""
    del lengths  # the position compare subsumes the length mask
    bsz, _, heads, d = q.shape
    k = gather_slots(k_pages, page_table, page_size).reshape(
        bsz, -1, heads, d)  # [B, S, H, D]
    v = gather_slots(v_pages, page_table, page_size).reshape(
        bsz, -1, heads, d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    cols = jnp.arange(k.shape[1], dtype=jnp.int32)
    # column j of the gathered view IS position j; bottom-right causal
    # masking plus unwritten/stale-slot exclusion in one compare.  -1e30,
    # not -inf: a fully-masked row (inactive slot) must stay NaN-free.
    s = s + jnp.where(
        cols[None, None, None, :] > positions[:, None, :, None], -1e30, 0.0
    )
    if window:
        s = s + jnp.where(
            cols[None, None, None, :] <= positions[:, None, :, None] - window,
            -1e30, 0.0)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _kernel_ok(q, k_pages, page_table, page_size):
    """Pages per block when the Pallas ragged kernel takes this call,
    else None: TPU backend and a shape the compiled kernel supports.
    Both serve dispatch widths (the pure-decode T=1 and the
    prefill-chunk T=C program) go through the same gate."""
    from unicore_tpu.ops.backend import pallas_interpret, use_pallas

    if not use_pallas():
        return None
    from unicore_tpu.ops.pallas import paged_attention as pl_pa

    if not pallas_interpret() and not pl_pa.supported(
        q.shape[2], q.shape[3], page_size, k_pages.dtype.itemsize
    ):
        return None
    return pl_pa.pick_pages_per_block(
        page_table.shape[1], page_size, q.shape[3],
        num_heads=q.shape[2], itemsize=k_pages.dtype.itemsize,
    )


def _kernel_choice(pages_per_block):
    """What the kernel was given, for ``dispatch_report()``'s description
    of a shape that took it: pages a block and K/V slots."""
    if pages_per_block is None:
        return ""
    from unicore_tpu.ops.pallas import paged_attention as pl_pa

    return " pp%d slots%d" % (pages_per_block, pl_pa.SLOTS)


def paged_attention(q, k_pages, v_pages, page_table, positions, lengths,
                    page_size, scale, window=0, three_pass=False):
    """Dispatching paged attention (see module docstring).  Without
    ``window`` / ``three_pass`` the call that reaches the kernel is the
    call it was."""
    from unicore_tpu.ops.backend import note_dispatch

    pages_per_block = _kernel_ok(q, k_pages, page_table, page_size)
    desc = "b%d w%d h%d d%d page%d %s%s%s%s" % (
        *q.shape, page_size, q.dtype.name, _kernel_choice(pages_per_block),
        " window%d" % window if window else "",
        " three-pass" if three_pass else "")
    if note_dispatch("ragged_paged_attention", desc,
                     pages_per_block is not None):
        from unicore_tpu.ops.pallas import paged_attention as pl_pa

        return pl_pa.ragged_paged_attention(
            q, k_pages, v_pages, page_table, positions, lengths,
            page_size=page_size, scale=scale,
            pages_per_block=pages_per_block, three_pass=three_pass,
            window=window,
        )
    return paged_attention_reference(
        q, k_pages, v_pages, page_table, positions, lengths, page_size,
        scale, window=window,
    )


def write_and_attend(q, k, v, k_pages, v_pages, paged, positions, scale,
                     window=0, three_pass=False):
    """One layer's paged step: this step's ``k``/``v`` (token-major,
    ``[..., H, D]`` over N tokens) scatter into the pool variables
    ``k_pages``/``v_pages`` (flax variables of collection ``"pagedkv"``,
    ``[num_slots, H*D]``) at ``paged.slot_mapping``, then every row
    attends the pages its table names.  The scatter lands before the
    gather, so a row sees the keys the same program wrote.

    Attention needs rows: ``q`` goes into the ``[B, width]`` rectangle
    the ragged kernel takes (``paged.to_rows``; position -1 masks a cell
    no token fills) and the output comes back in ``q``'s own layout.

    Grouped queries: ``k``/``v`` may hold fewer heads than ``q``, the
    pages are as wide as THEY are, and query head ``h`` reads K/V head
    ``h // g`` (``g`` query heads a K/V head).  The ``g`` heads of a
    group then ride as ``g`` query CELLS at one position, ``[B, T, kv *
    g, D] -> [B, T * g, kv, D]`` with the positions repeated, so the
    kernel runs as it is over ``kv`` heads and reads each page once for
    the heads that share it.  With ``g == 1`` nothing is folded.

    ``window`` > 0 (a sliding layer): ``k_pages`` / ``v_pages`` are the
    window kind's, written and read through the window table
    (:meth:`PagedMeta.windowed`), and a query sees ``window`` keys up to
    its own.  ``three_pass``: the kernel's float32 dots in three bfloat16
    passes, where a model's step asks for them (the kernel's module
    docstring)."""
    if window:
        paged = paged.windowed()
    width = k_pages.value.shape[-1]
    k_pages.value = k_pages.value.at[paged.slot_mapping].set(
        k.astype(k_pages.value.dtype).reshape(-1, width))
    v_pages.value = v_pages.value.at[paged.slot_mapping].set(
        v.astype(v_pages.value.dtype).reshape(-1, width))
    rows, row_positions = paged.to_rows(q), paged.row_positions(positions)
    if window:
        row_positions = paged.window_positions(row_positions)
    B, T, H, D = rows.shape
    g = H * D // width
    if g > 1:
        rows = rows.reshape(B, T, H // g, g, D).swapaxes(2, 3).reshape(
            B, T * g, H // g, D)
        row_positions = jnp.repeat(row_positions, g, axis=1)
    o = paged_attention(
        rows, k_pages.value, v_pages.value,
        page_table=paged.page_table, positions=row_positions,
        lengths=paged.lengths, page_size=paged.page_size, scale=scale,
        window=window, three_pass=three_pass,
    )
    if g > 1:
        o = o.reshape(B, T, g, H // g, D).swapaxes(2, 3).reshape(B, T, H, D)
    return paged.to_tokens(o, q.shape[:2])


# query cells one program of the ragged kernel holds in VMEM beside its
# page blocks: 512 x 640 float32 lanes is 1.3 MB a buffer (queries,
# outputs, accumulator), and PR 31's 512 cells of 512 lanes compiled where
# 1,024 did not
LATENT_QUERY_CELLS = 512


def write_latent_and_attend(q, entry, pages, paged, positions, scale,
                            value_lanes):
    """One latent-attention layer's paged step, in the ABSORBED form.

    ``entry`` [..., W] is what each of the step's N tokens leaves in the
    cache (normed latent | rotated rope key | zero lanes up to ``W``),
    scattered into ``pages`` (a flax variable of collection ``"pagedkv"``,
    ``[num_slots, W]``) at ``paged.slot_mapping`` before anything is read;
    ``q`` [..., H, W] are the heads' queries in the entries' own space
    (``W_kvb,K^T q_nope`` | rotated ``q_rope`` | zeros).  An entry is the
    key of every head and, in its first ``value_lanes`` lanes, the value:
    the ragged kernel runs over ONE K/V head of ``W`` lanes with the ``H``
    heads of a token as ``H`` query cells at its position, handed the one
    pool as keys and as values (it reads each page twice), and a row's
    context is never expanded to per-head keys and values.  Returns the
    heads' sums over the latents, ``[..., H, value_lanes]``; the caller
    applies ``W_kvb,V`` and ``W_o``.

    A decode step's row is one token: ``H`` cells.  A prefill chunk's
    ``T x H`` cells do not fit one program's VMEM, so the ``[B, T]``
    rectangle is cut into TILES of ``t`` tokens (``t x H <=
    LATENT_QUERY_CELLS``), each a row of the kernel with its row's page
    table and a length of its own, its last position + 1: a tile stops
    at its own causal edge and an empty one (every position -1) reads no
    page.  A one-token row of a mixed step takes a tile like any other
    row's.  Causal over ``positions``; position -1 masks a cell."""
    from unicore_tpu.ops.backend import note_dispatch

    W = pages.value.shape[-1]
    with jax.named_scope("mla_cache_write"):
        pages.value = pages.value.at[paged.slot_mapping].set(
            entry.astype(pages.value.dtype).reshape(-1, W))
    rows, row_positions = paged.to_rows(q), paged.row_positions(positions)
    B, T, H, _ = rows.shape
    form = "decode" if T == 1 else "prefill"
    t = max(1, min(T, LATENT_QUERY_CELLS // H))
    while T % t:
        t -= 1
    tiles = T // t
    cells = rows.reshape(B * tiles, t * H, 1, W)
    tile_positions = row_positions.reshape(B * tiles, t)
    lengths = jnp.max(tile_positions, axis=1) + 1
    cell_positions = jnp.repeat(tile_positions, H, axis=1)
    table = jnp.repeat(paged.page_table, tiles, axis=0)
    pages_per_block = _kernel_ok(cells, pages.value, table, paged.page_size)
    with jax.named_scope("mla_attend_" + form):
        if note_dispatch(
                "latent_attention_" + form,
                "b%d cells%d lanes%d page%d %s%s" % (
                    B * tiles, t * H, W, paged.page_size, q.dtype.name,
                    _kernel_choice(pages_per_block)),
                pages_per_block is not None):
            from unicore_tpu.ops.pallas import paged_attention as pl_pa

            o = pl_pa.ragged_paged_attention(
                cells, pages.value, pages.value, table, cell_positions,
                lengths, page_size=paged.page_size, scale=scale,
                pages_per_block=pages_per_block, three_pass=True)
        else:
            o = paged_attention_reference(
                cells, pages.value, pages.value, table, cell_positions,
                lengths, paged.page_size, scale)
    o = o.reshape(B, T, H, W)[..., :value_lanes]
    return paged.to_tokens(o, q.shape[:2])
