"""Paged KV-cache pool: host-side page accounting for the serve tier.

The device buffers (one ``[num_pages * page_size, H, Dh]`` k/v pair per
decoder layer, flax collection ``"pagedkv"``) are allocated ONCE at
engine init and donated through every jitted step — zero reallocation
after warmup.  This module owns everything about them that is NOT math:
which pages belong to which sequence, in what order, which are free —
and, since the multi-tenant refactor, which pages are SHARED between
sequences.  It is pure Python over ints, so the allocation invariants
are directly property-testable without a device.

Design notes (after "Ragged Paged Attention", arxiv 2604.15464, and the
vLLM paged-KV prefix-caching scheme):

- **Page 0 is reserved as the trash page.**  Jitted steps always run at
  a fixed batch/width, so inactive batch rows and padded prompt
  positions still produce k/v writes; their ``slot_mapping`` entries
  point into page 0, which no sequence ever owns and no mask ever
  admits.  That keeps every scatter in-bounds without per-row cond.
- Page tables are append-only per sequence: token at position ``p``
  lives in the sequence's ``p // page_size``-th page at offset
  ``p % page_size``, so the flat gathered layout is position-ordered by
  construction and the causal mask is a plain position compare.
- ``alloc``/``extend``/``free`` enforce strict invariants (no
  unaccounted aliasing, no double-free, exhaustion raises
  :class:`PoolExhausted`) instead of degrading silently — the
  scheduler's eviction logic is built on top of these exceptions.

Shared-prefix dedup (multi-tenant pool):

- Pages are REFCOUNTED.  A FULL page whose tokens are a prefix of a
  registered prompt is indexed by a stable chain digest
  (blake2b over ``prev_digest || page tokens`` — never Python's salted
  ``hash()``), so a later sequence opening with the same tokens gets
  that page by table reference instead of re-prefilling it:
  ``alloc(..., tokens=...)`` matches the longest indexed chain and the
  engine skips the KV writes for the matched tokens entirely.
- **Only full, immutable pages are ever shared.**  The match is capped
  at ``len(tokens) - 1`` so at least one token (the one whose logits
  seed sampling) is always re-prefilled, and the page holding it — the
  partial/boundary tail — is always privately owned: the tail's shared
  content is recomputed into the private copy on first write
  (copy-on-write by recompute), so one sequence's decode writes can
  never mutate another's shared page.  Structurally: every write a
  sequence issues lands at a position ``>= cached_tokens``, and those
  positions map into pages past the shared run.
- A freed page whose refcount hits zero RETURNS TO THE CACHE if it is
  registered (LRU-ordered), not to the free list: a drained engine
  keeps a warm prefix cache (``is_idle`` counts cached pages as free
  capacity).  Allocation takes the free list first, then evicts cached
  pages oldest-first — deterministic, so a replayed trace makes the
  same eviction (and therefore the same hit/miss) decisions every run.

Recurrent state slots (a model with linear-attention layers):

- Such a model keeps, beside its pages, ONE fixed-size state per
  sequence (``state_slots > 0``: as many slots as the engine has batch
  rows).  A slot is taken in :meth:`alloc` and released in :meth:`free`,
  so it lives and dies with the sequence's pages through every path
  that ends a sequence's residency (finish, preemption, expiry, drain,
  failover salvage): nothing else hands slots out.  The device side
  zeroes a state when a row starts at position 0, so a released slot
  needs no clearing and a re-admitted sequence prefills from zero.
- A prefix hit would start a prompt past its shared pages, where a
  recurrent layer has no state: the engine builds this pool with
  ``prefix_cache=False`` for such a model (state snapshots at page
  boundaries are what a hit would need).

Two kinds of page (a model with sliding-window layers, ``window > 0``):

- Its global layers keep every token, in the pages above.  A sliding
  layer's query at position ``p`` sees the keys ``p - window < j <= p``
  and nothing older, so its layers' pages are a SECOND KIND with device
  arrays, free list and accounting of their own: a sequence holds a
  table of each kind, and the window kind's is TRIMMED.  It holds the
  pages from ``window_first`` (a page index, ``position // page_size``)
  to the page of the last position planned, in position order, so that
  the token at position ``p`` lies in entry ``p // page_size -
  window_first`` at offset ``p % page_size``: "column j is position j"
  holds in the frame that starts at ``window_first * page_size``, which
  is all the kernel's masks need (``serve/attention.py``
  ``PagedMeta.window_base``).  A ring of a fixed number of pages would
  hold the same bytes, but position ``p`` would lie at column ``p mod
  ring``: both masks would need the modulus, a row's walk would wrap, and
  the one kernel every model shares would carry it.  Trimming keeps the
  kernel's compare and moves the bookkeeping here, to the host.
- :meth:`window_extend` takes window pages up to the last position a step
  plans to write; :meth:`window_release` hands back, at the step
  boundary, every page no query still to come can see: all of its
  positions are ``<= next position - window``.  Both are counted
  (``window_stats``).  A released page may be handed to another row in
  the very NEXT step, while the step that last read it may still be
  running or even queued behind one (the engine launches a step ahead):
  that is safe because the device runs the steps it is handed in the
  order they were launched, one at a time, each with the pool arrays the
  step before it returned (they are donated from step to step), so the
  new owner's write is ordered after the old owner's last read by the
  data dependency itself.  Nothing on the host has to wait.  The same
  holds for the pages :meth:`free` returns when a sequence ends or is
  preempted with a step in flight.
- Capacity is counted, not hoped for: every resident sequence RESERVES
  :attr:`window_reserve` pages (what a row needs to see its window and
  write one more page) and the pool keeps ``window_slack`` more for the
  tokens one step carries beyond that (a prompt's chunks).  Admission
  asks both kinds: :meth:`can_alloc` refuses a sequence the window kind
  could not guarantee its reserve.  The engine sizes the window kind for
  a full batch, so there a free row implies window pages; a smaller pool
  simply admits fewer.  :meth:`window_extend` still raises
  :class:`PoolExhausted` if the pages are not there.
- Prefix hits are refused for such a model (the engine builds the pool
  with ``prefix_cache=False``): a hit starts a prompt at the matched
  point, where a sliding layer needs the ``window - 1`` tokens before it.
  Their window pages were released long ago.  A hit would need them
  still held (the last ``window`` tokens' window pages registered with
  the shared global pages, and kept while the prefix is cached) or
  recomputed (re-prefill the last ``window - 1`` tokens of the match into
  the window kind only, skipping the global layers' writes).  Neither is
  built.
- For a model with no window none of this exists: no second free list,
  no walk, and the tables and the step's operands are what they were.
"""

import hashlib
from collections import OrderedDict


class PoolExhausted(Exception):
    """Raised when an alloc/extend needs more free pages than exist."""


def _page_digest(prev, tokens):
    """Stable chain digest of one full page of token ids: blake2b over
    the previous page's digest plus this page's tokens — process-stable
    (never the salted built-in ``hash()``), so two sequences, two runs,
    or two replicas agree on what a shared prefix is."""
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(b"|".join(str(int(t)).encode() for t in tokens))
    return h.digest()


def window_reserve_pages(window, page_size):
    """Window pages a resident sequence reserves: what a row holds to see
    ``window`` keys ending anywhere in a page, and one more page for the
    rounding of the tokens it writes (0: no window)."""
    return (window - 2) // page_size + 3 if window else 0


def window_kind_for(window, page_size, rows, step_tokens):
    """The window kind of a pool whose engine has ``rows`` batch rows and
    carries ``step_tokens`` tokens a step, as :class:`PagedKVPool`'s
    keywords: every row's reserve, the pages one step's tokens add, the
    trash page.  ``{}`` for a model without a window."""
    if not window:
        return {}
    slack = -(-int(step_tokens) // page_size)
    return {"window": window, "window_slack": slack,
            "num_window_pages": 1 + rows * window_reserve_pages(
                window, page_size) + slack}


class PagedKVPool:
    """Fixed-capacity refcounted page allocator with per-sequence page
    tables and an optional shared-prefix page index."""

    def __init__(self, num_pages, page_size, prefix_cache=True,
                 state_slots=0, window=0, num_window_pages=0,
                 window_slack=0):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "reserved trash page)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.prefix_cache = bool(prefix_cache)
        # LIFO free list keeps recently-freed (cache-warm) pages hot
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._tables = {}  # seq_id -> [page, ...] in position order
        self._lens = {}    # seq_id -> token count
        self._refs = {}    # page -> number of tables referencing it
        # prefix index: chain digest -> page (full prompt pages only);
        # _cached holds registered pages with refcount 0 in LRU order
        # (oldest first = next evicted)
        self._index = {}        # digest -> page
        self._page_digests = {}  # page -> digest (registered pages)
        self._cached = OrderedDict()  # page -> digest, LRU order
        self._shared_tokens = {}  # seq_id -> tokens satisfied by dedup
        # recurrent state slots: one per resident sequence (docstring)
        self.num_state_slots = int(state_slots)
        self._state_free = list(range(self.num_state_slots - 1, -1, -1))
        self._state_of = {}  # seq_id -> slot
        self.state_stats = {"taken": 0, "peak": 0}
        # last _match_chain result, keyed by (tokens, cap) + an index
        # generation counter: admission calls can_alloc then alloc with
        # the same prompt back to back, and the blake2b chain walk is
        # the expensive part of the hot admission path
        self._match_memo = None
        self._index_gen = 0
        # the window kind (docstring): tokens a sliding layer sees, its
        # pages (page 0 the trash page, as above), the pages kept beyond
        # the residents' reserves
        self.window = int(window)
        self.num_window_pages = int(num_window_pages)
        self.window_slack = int(window_slack)
        if self.window:
            if self.prefix_cache:
                raise ValueError(
                    "a pool with a window kind takes no prefix hits "
                    "(prefix_cache=False): a hit would start behind "
                    "window pages released long ago")
            if self.num_window_pages < 1 + self.window_reserve \
                    + self.window_slack:
                raise ValueError(
                    f"num_window_pages {self.num_window_pages} cannot "
                    f"hold one sequence's reserve of {self.window_reserve}"
                    f" pages beside the slack of {self.window_slack} and "
                    "the trash page")
        self._window_free = list(range(self.num_window_pages - 1, 0, -1))
        self._window_tables = {}   # seq_id -> [page, ...], trimmed
        self._window_first = {}    # seq_id -> page index of the table's [0]
        self.window_stats = {"taken": 0, "released": 0, "row_pages_peak": 0}
        self.prefix_stats = {
            "lookups": 0, "hits": 0, "tokens_saved": 0,
            "pages_shared": 0, "cache_evictions": 0,
        }

    # -- capacity ------------------------------------------------------

    @property
    def num_usable_pages(self):
        return self.num_pages - 1

    @property
    def num_free_pages(self):
        """Allocatable pages: the free list plus reclaimable cached
        prefix pages (refcount 0) — cache residency never shrinks the
        pool's capacity, it only changes what a miss costs."""
        return len(self._free) + len(self._cached)

    def occupancy(self):
        """Fraction of usable pages currently allocated (cached-free
        prefix pages count as free)."""
        used = self.num_usable_pages - self.num_free_pages
        return used / self.num_usable_pages

    def pages_for(self, num_tokens):
        """Pages a sequence of ``num_tokens`` tokens occupies."""
        return -(-int(num_tokens) // self.page_size)

    def _match_chain(self, tokens, num_tokens):
        """(shared_pages, [page, ...]) — the longest indexed chain run
        over ``tokens``' full pages, capped so at least one token stays
        un-matched (the tail is always re-prefilled privately).
        Memoized across the back-to-back can_alloc/alloc pair of one
        admission (invalidated whenever the index mutates)."""
        if not self.prefix_cache or tokens is None:
            return 0, []
        cap = (int(num_tokens) - 1) // self.page_size
        key = (tuple(tokens[:cap * self.page_size]), cap)
        if (self._match_memo is not None
                and self._match_memo[0] == key
                and self._match_memo[1] == self._index_gen):
            n, pages = self._match_memo[2]
            return n, list(pages)
        pages = []
        digest = b""
        for i in range(cap):
            digest = _page_digest(
                digest, tokens[i * self.page_size:(i + 1) * self.page_size]
            )
            page = self._index.get(digest)
            if page is None:
                break
            pages.append(page)
        self._match_memo = (key, self._index_gen, (len(pages), list(pages)))
        return len(pages), pages

    def _new_page_budget(self, shared_pages):
        """Pages available for FRESH allocation alongside a matched
        chain: matched pages currently parked in the cache stop being
        reclaimable the moment they are re-referenced, so they must not
        double-count as free capacity."""
        cached_matched = sum(1 for p in shared_pages if p in self._cached)
        return len(self._free) + len(self._cached) - cached_matched

    def can_alloc(self, num_tokens, tokens=None):
        """Whether a new sequence of ``num_tokens`` tokens fits —
        with ``tokens`` the check credits shared-prefix pages the
        allocation would not actually consume.  Both kinds of page are
        asked: the window kind must be able to guarantee one more
        resident its reserve."""
        if self.num_state_slots and not self._state_free:
            return False
        if self.window and not self._window_admits(len(self._tables) + 1):
            return False
        need = self.pages_for(num_tokens)
        shared, shared_pages = self._match_chain(tokens, num_tokens)
        return need - shared <= self._new_page_budget(shared_pages)

    def is_idle(self):
        """True iff no sequence holds pages and every usable page is
        free or cached-reclaimable — what a drained engine's pool must
        look like (the drain report and chaos harness assert it
        alongside :meth:`check_invariants`); a warm prefix cache is
        idle by design."""
        return (not self._tables
                and self.num_free_pages == self.num_usable_pages
                and len(self._window_free)
                == max(self.num_window_pages - 1, 0))

    # -- page acquisition ----------------------------------------------

    def _take_page(self):
        """One free page: the free list first (LIFO), then the OLDEST
        cached prefix page — deterministic eviction, so replayed traces
        make identical hit/miss decisions."""
        if self._free:
            return self._free.pop()
        page, digest = self._cached.popitem(last=False)
        del self._index[digest]
        del self._page_digests[page]
        self._index_gen += 1
        self.prefix_stats["cache_evictions"] += 1
        return page

    def _acquire_shared(self, pages):
        """Take refcounts on matched chain pages (pulling any cached
        ones back into service)."""
        for p in pages:
            self._refs[p] = self._refs.get(p, 0) + 1
            if p in self._cached:
                del self._cached[p]

    def _release(self, page):
        """Drop one reference; a zero-ref registered page parks in the
        cache (MRU end), anything else returns to the free list."""
        self._refs[page] -= 1
        if self._refs[page] > 0:
            return
        del self._refs[page]
        if page in self._page_digests:
            self._cached[page] = self._page_digests[page]
        else:
            self._free.append(page)

    # -- alloc / extend / free -----------------------------------------

    def alloc(self, seq_id, num_tokens, tokens=None):
        """Allocate pages for a new sequence of ``num_tokens`` tokens.

        With ``tokens`` (the sequence's token ids) and the prefix cache
        on, full pages matching a registered prefix chain are SHARED by
        reference; :meth:`cached_tokens` reports how many leading
        tokens' KV already exists, so the caller can skip prefilling
        them."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        shared, shared_pages = self._match_chain(tokens, num_tokens)
        need = self.pages_for(num_tokens) - shared
        if need > self._new_page_budget(shared_pages):
            raise PoolExhausted(
                f"need {need} new pages for {num_tokens} tokens "
                f"({shared} shared), "
                f"{self._new_page_budget(shared_pages)} free"
            )
        if self.window and not self._window_admits(len(self._tables) + 1):
            raise PoolExhausted(
                f"the window kind cannot guarantee sequence {seq_id!r} its "
                f"reserve of {self.window_reserve} pages "
                f"({len(self._tables)} resident, "
                f"{self.num_window_pages - 1} window pages)")
        self._take_state_slot(seq_id)
        if self.window:
            self._window_tables[seq_id] = []
            self._window_first[seq_id] = 0
        self._acquire_shared(shared_pages)
        table = list(shared_pages)
        for _ in range(need):
            p = self._take_page()
            self._refs[p] = self._refs.get(p, 0) + 1
            table.append(p)
        self._tables[seq_id] = table
        self._lens[seq_id] = int(num_tokens)
        self._shared_tokens[seq_id] = shared * self.page_size
        if tokens is not None and self.prefix_cache:
            self.prefix_stats["lookups"] += 1
            if shared:
                self.prefix_stats["hits"] += 1
                self.prefix_stats["tokens_saved"] += shared * self.page_size
                self.prefix_stats["pages_shared"] += shared
        return list(table)

    def cached_tokens(self, seq_id):
        """How many leading tokens of this sequence's last ``alloc``
        were satisfied by shared-prefix pages (their KV already exists;
        prefill starts past them)."""
        return self._shared_tokens.get(seq_id, 0)

    def extend(self, seq_id, num_tokens=1):
        """Grow a sequence by ``num_tokens``; allocates new pages only
        when a token crosses a page boundary."""
        if seq_id not in self._tables:
            raise KeyError(f"sequence {seq_id!r} not allocated")
        new_len = self._lens[seq_id] + int(num_tokens)
        need = self.pages_for(new_len) - len(self._tables[seq_id])
        if need > self.num_free_pages:
            raise PoolExhausted(
                f"sequence {seq_id!r} needs {need} more page(s), "
                f"{self.num_free_pages} free"
            )
        for _ in range(max(need, 0)):
            p = self._take_page()
            self._refs[p] = self._refs.get(p, 0) + 1
            self._tables[seq_id].append(p)
        self._lens[seq_id] = new_len
        return list(self._tables[seq_id])

    def free(self, seq_id):
        """Drop all of a sequence's page references.  Exclusive
        unregistered pages return to the free list; registered pages
        whose last reference this was park in the prefix cache."""
        if seq_id not in self._tables:
            raise KeyError(f"sequence {seq_id!r} not allocated "
                           "(double free?)")
        pages = self._tables.pop(seq_id)
        del self._lens[seq_id]
        self._shared_tokens.pop(seq_id, None)
        if self.num_state_slots:
            self._state_free.append(self._state_of.pop(seq_id))
        if self.window:
            del self._window_first[seq_id]
            self._window_free.extend(
                reversed(self._window_tables.pop(seq_id)))
        for p in reversed(pages):
            self._release(p)
        return pages

    # -- the window kind -----------------------------------------------

    @property
    def window_reserve(self):
        return window_reserve_pages(self.window, self.page_size)

    def window_row_pages(self, chunk):
        """The most window pages ONE row of ``chunk`` query tokens
        addresses: the keys ``window - 1`` before its first query to its
        last, wherever they fall in their pages."""
        return (self.window + int(chunk) - 2) // self.page_size + 2

    def _window_admits(self, residents):
        return (residents * self.window_reserve + self.window_slack
                <= self.num_window_pages - 1)

    @property
    def window_pages_in_use(self):
        return max(self.num_window_pages - 1, 0) - len(self._window_free)

    @property
    def global_pages_in_use(self):
        return self.num_usable_pages - self.num_free_pages

    def window_extend(self, seq_id, upto):
        """Hold window pages through position ``upto - 1`` (the last one
        the step being planned writes)."""
        table = self._window_tables[seq_id]
        need = (self.pages_for(upto) - self._window_first[seq_id]
                - len(table))
        if need > len(self._window_free):
            raise PoolExhausted(
                f"sequence {seq_id!r} needs {need} more window page(s), "
                f"{len(self._window_free)} free")
        for _ in range(need):
            table.append(self._window_free.pop())
        self.window_stats["taken"] += max(need, 0)

    def window_release(self, seq_id, next_position):
        """Hand back every window page no query still to come can see:
        the next query sits at ``next_position`` and sees nothing at or
        before ``next_position - window``.  Returns how many went."""
        first = self._window_first[seq_id]
        keep_from = max(first,
                        (int(next_position) - self.window + 1)
                        // self.page_size)
        table = self._window_tables[seq_id]
        gone = min(keep_from - first, len(table))
        if gone <= 0:
            return 0
        self._window_free.extend(reversed(table[:gone]))
        del table[:gone]
        # a table trimmed to nothing restarts at the first page still seen
        self._window_first[seq_id] = first + gone if table else keep_from
        self.window_stats["released"] += gone
        return gone

    def window_view(self, seq_id, start, end):
        """``(pages, base)`` of a row whose queries sit at ``start ..
        end - 1``: the window pages from the one with the first key its
        first query sees to the one its last token is written to, and
        the position of the first slot of the first of them."""
        first = self._window_first[seq_id]
        table = self._window_tables[seq_id]
        lo = max(first, max(int(start) - self.window + 1, 0)
                 // self.page_size)
        hi = (int(end) - 1) // self.page_size
        if hi - first >= len(table):
            raise IndexError(
                f"position {end - 1} beyond the window pages of sequence "
                f"{seq_id!r} (pages {first}..{first + len(table) - 1})")
        pages = table[lo - first:hi - first + 1]
        self.window_stats["row_pages_peak"] = max(
            self.window_stats["row_pages_peak"], len(pages))
        return pages, lo * self.page_size

    # -- recurrent state slots -----------------------------------------

    def _take_state_slot(self, seq_id):
        if not self.num_state_slots:
            return
        if not self._state_free:
            raise PoolExhausted(
                f"no free state slot for sequence {seq_id!r} "
                f"({self.num_state_slots} held)")
        self._state_of[seq_id] = self._state_free.pop()
        self.state_stats["taken"] += 1
        self.state_stats["peak"] = max(self.state_stats["peak"],
                                       len(self._state_of))

    def state_slot(self, seq_id):
        """The state-store slot of a resident sequence."""
        return self._state_of[seq_id]

    # -- prefix registration -------------------------------------------

    def register_prefix(self, seq_id, tokens):
        """Index this sequence's full pages covering ``tokens`` (the
        engine calls this once the prompt's KV is fully written) so
        later sequences sharing the prefix dedup against them.  Only
        pages whose every slot is already written (full pages strictly
        inside ``tokens``) are registered — the partial tail stays
        private.  Returns the number of newly indexed pages."""
        if not self.prefix_cache:
            return 0
        table = self._tables.get(seq_id)
        if table is None:
            raise KeyError(f"sequence {seq_id!r} not allocated")
        if len(tokens) > self._lens[seq_id]:
            raise ValueError(
                f"cannot register {len(tokens)} tokens for sequence "
                f"{seq_id!r} holding {self._lens[seq_id]}"
            )
        registered = 0
        digest = b""
        for i in range(len(tokens) // self.page_size):
            digest = _page_digest(
                digest, tokens[i * self.page_size:(i + 1) * self.page_size]
            )
            page = table[i]
            if digest in self._index:
                # a concurrent prompt already owns this chain entry; a
                # second registration would alias one digest to two
                # pages — keep the first, this page stays private
                continue
            if page in self._page_digests:
                continue  # already indexed (a shared page we matched)
            self._index[digest] = page
            self._page_digests[page] = digest
            registered += 1
        if registered:
            self._index_gen += 1
        return registered

    # -- lookups -------------------------------------------------------

    def page_table(self, seq_id):
        return list(self._tables[seq_id])

    def seq_len(self, seq_id):
        return self._lens[seq_id]

    def seq_ids(self):
        return list(self._tables)

    def slot(self, seq_id, position):
        """Flat pool slot (page * page_size + offset) of ``position``."""
        table = self._tables[seq_id]
        page_idx, offset = divmod(int(position), self.page_size)
        if page_idx >= len(table):
            raise IndexError(
                f"position {position} beyond the {len(table)} page(s) of "
                f"sequence {seq_id!r}"
            )
        return table[page_idx] * self.page_size + offset

    def check_invariants(self):
        """Internal-consistency audit (cheap; tests call it after every
        mutation): refcount property (every reference accounted, shared
        pages only within registered prefixes), free/cached/referenced
        partition, lengths vs table sizes, trash page never handed
        out."""
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate pages in free list"
        cached = set(self._cached)
        assert not free & cached, "page both free and cached"
        counted = {}
        for sid, table in self._tables.items():
            assert self.pages_for(self._lens[sid]) == len(table), (
                sid, self._lens[sid], table)
            shared_pages = -(-self._shared_tokens.get(sid, 0)
                             // self.page_size)
            for i, p in enumerate(table):
                assert p not in free and p not in cached, (
                    f"page {p} referenced by {sid!r} but free/cached")
                counted[p] = counted.get(p, 0) + 1
                if counted[p] > 1 or self._refs.get(p, 0) > 1:
                    # multi-referenced pages must be registered prefix
                    # pages or this sequence's matched shared run
                    assert (p in self._page_digests
                            or i < shared_pages), (
                        f"page {p} aliased outside the prefix index")
        assert counted == self._refs, (counted, self._refs)
        for digest, page in self._index.items():
            assert self._page_digests.get(page) == digest, (
                f"index/digest maps disagree on page {page}")
            assert page in cached or page in counted, (
                f"indexed page {page} is neither cached nor referenced")
        for page, digest in self._cached.items():
            assert self._index.get(digest) == page, (
                f"cached page {page} not in the index")
        seen = free | cached | set(counted)
        assert 0 not in seen, "trash page 0 was handed out"
        assert seen == set(range(1, self.num_pages)), "pages leaked"
        if self.num_state_slots:
            held = list(self._state_of.values())
            assert len(set(held)) == len(held), "state slot held twice"
            assert not set(held) & set(self._state_free), (
                "state slot both held and free")
            assert (set(held) | set(self._state_free)
                    == set(range(self.num_state_slots))), "state slot leaked"
            assert len(self._state_free) + len(held) == self.num_state_slots
            assert set(self._state_of) == set(self._tables), (
                "a state slot must live and die with its sequence's pages")
        if self.window:
            wfree = set(self._window_free)
            assert len(wfree) == len(self._window_free), (
                "duplicate pages in the window free list")
            held = [p for t in self._window_tables.values() for p in t]
            assert len(set(held)) == len(held), "window page held twice"
            assert not set(held) & wfree, "window page both held and free"
            assert 0 not in wfree and 0 not in held, (
                "window trash page 0 was handed out")
            assert wfree | set(held) == set(
                range(1, self.num_window_pages)), "window pages leaked"
            assert (set(self._window_tables) == set(self._window_first)
                    == set(self._tables)), (
                "a window table must live and die with its sequence")
            assert self._window_admits(len(self._tables)), (
                "more residents than the window kind can guarantee")
            for sid, table in self._window_tables.items():
                # never past the sequence's own length, never a page
                # wholly behind what was released
                assert (self._window_first[sid] + len(table)
                        <= self.pages_for(self._lens[sid])), (sid, table)
