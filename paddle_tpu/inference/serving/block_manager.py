"""Paged KV block pool: the memory half of the serving subsystem.

Reference frame: vLLM's BlockSpaceManager / PaddleNLP's block-attention
cache pool — the allocator that lets `block_multihead_attention_` serve a
ragged request mix from one fixed pool of fixed-size cache pages instead
of per-slot max_len reservations:

- fixed ``block_size`` pages, allocated/freed with **ref-counting** so
  several sequences can map the same physical page;
- per-sequence **block tables** (the [B, max_blocks] int32 rows the paged
  kernel consumes, -1 = unassigned);
- a **hash-keyed prefix cache**: every full block is content-addressed by
  the rolling hash of all tokens up to its end, so a new request whose
  prompt shares a prefix with anything the pool has seen maps those pages
  instead of recomputing them. Full-block hits share pages by refcount;
  a partial hit on the following block is served **copy-on-write**: the
  manager hands out a private copy (the engine executes the device-side
  page copy from :meth:`take_copies`) and the matched tokens still skip
  recompute;
- freed-but-cached pages park in an LRU side pool and keep serving prefix
  hits until allocation pressure reclaims them (hash entries drop at
  reclaim, never silently);
- utilization accounting for the observability gauges and the
  scheduler's admission/preemption decisions;
- for a model with sliding-window layers, a **second pool with a second
  lifetime** (`window_blocks`, `window`): a sequence has one more block
  table over it, whose pages come a chunk at a time (`ensure_capacity`) and
  go back to the free list once every position in them lies more than
  `window` - 1 behind the sequence's computed length (`release_behind`):
  no query to come can see them. The table keeps its logical width, the
  released entries read -1. Such pages hold the keys of the window layers
  alone, so the prefix cache is off: a hit on the full pool's pages would
  find no window keys beside them;
- for a model with recurrent state (state-space layers), a **second kind
  of cache**: `state_slots` slots, one a running sequence, taken where the
  sequence's pages are mapped (`allocate_sequence` calls `take_slot`: no
  free slot is no admission, as no free page is) and given back with them
  (`free_sequence`: finish, cancel, preemption). A slot holds a sum over
  the whole sequence, which no page hit can restore, so the prefix cache
  is off here too and a preempted sequence is recomputed from its ids from
  a zero state. The engine owns the state arrays; a slot is an index into
  them.

Pure host-side bookkeeping: no jax imports, no device state. The engine
owns the actual [num_blocks, KV, block_size, hd] cache arrays; block ids
here index those arrays.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["BlockManager", "NoFreeBlocksError"]


class NoFreeBlocksError(RuntimeError):
    """The pool cannot satisfy an allocation — the scheduler's signal to
    preempt (never surfaced to clients; admission checks first)."""


def _chain_hash(prev_hash: int, tokens: Tuple[int, ...]) -> int:
    return hash((prev_hash, tokens))


class BlockManager:
    def __init__(self, num_blocks: int, block_size: int,
                 page_bytes: int = 0, hit_multiple: int = 1,
                 window_blocks: int = 0, window: int = 0,
                 window_page_bytes: int = 0, state_slots: int = 0,
                 state_slot_bytes: int = 0):
        if num_blocks < 1 or block_size < 1:
            raise ValueError(f"need num_blocks>=1 and block_size>=1, got "
                             f"{num_blocks}/{block_size}")
        if hit_multiple < 1 or block_size % hit_multiple:
            raise ValueError(
                f"block_size={block_size} must be a multiple of "
                f"hit_multiple={hit_multiple} (the model's block_length)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # a prefix hit is cut down to a multiple of this: under generation
        # by diffusion over blocks (the engine passes the model's
        # block_length) the keys and values inside a block depend on the
        # whole block, so a block matched in part is not matched
        self.hit_multiple = int(hit_multiple)
        # dtype-aware device footprint of one page across all layers, both
        # cache sides (+ per-page scales when quantized) — supplied by the
        # engine so byte gauges and router placement stay truthful when
        # int8 pages make a "block" 2-4x cheaper than its fp32 twin
        self.page_bytes = int(page_bytes)
        # the window pool (0 blocks: the model has no window layer and
        # nothing below differs from what it was): its free list, each
        # sequence's table over it (-1 where a page went back) and how many
        # leading entries of it were released
        if bool(window_blocks) != bool(window):
            raise ValueError("window_blocks and window come together")
        self.window_blocks = int(window_blocks)
        self.window = int(window)
        self.window_page_bytes = int(window_page_bytes)
        self._wfree: List[int] = list(range(self.window_blocks))[::-1]
        self._wtables: Dict[int, List[int]] = {}
        self._wreleased: Dict[int, int] = {}
        # the state slots (0: the model has no recurrent state and nothing
        # below differs from what it was): the free ones and each
        # sequence's
        self.state_slots = int(state_slots)
        self.state_slot_bytes = int(state_slot_bytes)
        self._sfree: List[int] = list(range(self.state_slots))[::-1]
        self._slots: Dict[int, int] = {}
        self._free: List[int] = list(range(num_blocks))[::-1]  # pop() = lowest
        self._refs: Dict[int, int] = {}
        # content-addressed full blocks: chain hash -> block id, the inverse
        # (so frees drop entries without scanning), and the chunk content
        # (prev_hash, tokens) behind each hash for partial/COW matching
        self._hash_to_block: Dict[int, int] = {}
        self._block_hash: Dict[int, int] = {}
        self._hash_info: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
        # chain hash -> the hashes registered behind it, in the order they
        # came (a dict as an ordered set): what a partial match looks
        # through, instead of every page the cache holds
        self._children: Dict[int, Dict[int, None]] = {}
        # refcount-0 blocks still holding cached KV, oldest first (LRU)
        self._cached_free: "OrderedDict[int, None]" = OrderedDict()
        # per-sequence block tables, and how far each is content-addressed:
        # (leading pages hashed, chain hash through them), where
        # register_computed goes on
        self._tables: Dict[int, List[int]] = {}
        self._hashed: Dict[int, Tuple[int, int]] = {}
        # pending device copies (src, dst) the engine must execute before
        # the next step touches dst. src pages are ref-pinned while a copy
        # is pending so allocation pressure cannot reclaim (and another
        # sequence reuse) the source before the device copy runs; the pin
        # is released by take_copies() or by purging the pair when the
        # owning sequence is freed first (cancel mid-chunked-prefill).
        self._pending_copies: List[Tuple[int, int]] = []
        # optional () -> (in_use, total) callback for NON-KV paged device
        # residency sharing this pool's byte gauges (today: the
        # AdapterManager's slot packs) — so a replica stuffed with
        # adapters is never scored as empty by the router's byte tiebreak
        self.extra_bytes = None
        self.stats = {"allocs": 0, "frees": 0, "prefix_hit_blocks": 0,
                      "prefix_hit_tokens": 0, "cow_copies": 0,
                      "cache_evictions": 0, "cow_purged": 0,
                      "adopted_pages": 0}
        if self.window_blocks:
            self.stats.update(window_allocs=0, window_released=0)

    # -- capacity ---------------------------------------------------------
    def num_free(self) -> int:
        return len(self._free) + len(self._cached_free)

    def num_allocated(self) -> int:
        return self.num_blocks - self.num_free()

    def window_allocated(self) -> int:
        return self.window_blocks - len(self._wfree)

    def utilization(self) -> float:
        """Pages allocated over pages held, both pools together."""
        return ((self.num_allocated() + self.window_allocated())
                / (self.num_blocks + self.window_blocks))

    def bytes_total(self) -> int:
        """Device bytes of the page pools (0 when the engine did not
        report a page size — e.g. unit tests building bare managers),
        plus any registered extra paged residency (adapter slot packs)."""
        extra = self.extra_bytes()[1] if self.extra_bytes else 0
        return (self.num_blocks * self.page_bytes
                + self.window_blocks * self.window_page_bytes
                + self.state_slots * self.state_slot_bytes + extra)

    def bytes_in_use(self) -> int:
        """Device bytes behind allocated pages, dtype-aware, plus any
        registered extra paged residency (adapter slot packs)."""
        extra = self.extra_bytes()[0] if self.extra_bytes else 0
        return (self.num_allocated() * self.page_bytes
                + self.window_allocated() * self.window_page_bytes
                + self.slots_live() * self.state_slot_bytes + extra)

    def blocks_needed(self, num_tokens: int) -> int:
        return -(-int(num_tokens) // self.block_size)

    def can_allocate(self, n_blocks: int, n_window: int = 0,
                     n_slots: int = 0) -> bool:
        return (self.num_free() >= n_blocks
                and len(self._wfree) >= n_window
                and len(self._sfree) >= n_slots)

    # -- state slots ------------------------------------------------------
    def slots_live(self) -> int:
        return self.state_slots - len(self._sfree)

    def take_slot(self, seq_id: int) -> int:
        """Give `seq_id` a free state slot (what it holds is stale: the
        sequence starts from zeros by its `past == 0`, not by a clear)."""
        if not self._sfree:
            raise NoFreeBlocksError(
                f"no free state slot for sequence {seq_id}: all "
                f"{self.state_slots} hold a running sequence")
        self._slots[seq_id] = self._sfree.pop()
        return self._slots[seq_id]

    def slot_of(self, seq_id: int) -> int:
        return self._slots[seq_id]

    def growth(self, seq_id: int, num_tokens: int) -> Tuple[int, int]:
        """(pages of the pool, pages of the window pool) that
        `ensure_capacity(seq_id, num_tokens)` would take."""
        need = self.blocks_needed(num_tokens)
        return (max(0, need - len(self._tables[seq_id])),
                max(0, need - len(self._wtables[seq_id]))
                if self.window_blocks else 0)

    # -- raw page pool ----------------------------------------------------
    def _drop_hash(self, blk: int):
        h = self._block_hash.pop(blk, None)
        if h is not None:
            if self._hash_to_block.get(h) == blk:
                del self._hash_to_block[h]
            info = self._hash_info.pop(h, None)
            if info is not None:
                kids = self._children[info[0]]
                del kids[h]
                if not kids:
                    del self._children[info[0]]

    def _index_hash(self, h: int, blk: int, prev_h: int,
                    chunk: Tuple[int, ...]):
        self._hash_to_block[h] = blk
        self._block_hash[blk] = h
        self._hash_info[h] = (prev_h, chunk)
        self._children.setdefault(prev_h, {})[h] = None

    def _take_free(self) -> int:
        if self._free:
            return self._free.pop()
        if self._cached_free:  # reclaim the LRU cached page
            blk, _ = self._cached_free.popitem(last=False)
            self._drop_hash(blk)
            self.stats["cache_evictions"] += 1
            return blk
        raise NoFreeBlocksError(
            f"KV pool exhausted: {self.num_blocks} blocks x "
            f"{self.block_size} tokens all referenced")

    def _alloc_block(self) -> int:
        blk = self._take_free()
        self._refs[blk] = 1
        self.stats["allocs"] += 1
        return blk

    def _incref(self, blk: int):
        if blk in self._cached_free:           # revive a parked cached page
            del self._cached_free[blk]
            self._refs[blk] = 1
        else:
            self._refs[blk] += 1

    def _decref(self, blk: int):
        self._refs[blk] -= 1
        if self._refs[blk] > 0:
            return
        del self._refs[blk]
        self.stats["frees"] += 1
        if blk in self._block_hash:            # keep serving prefix hits
            self._cached_free[blk] = None
        else:
            self._free.append(blk)

    # -- sequence lifecycle -----------------------------------------------
    def allocate_sequence(self, seq_id: int, tokens: Sequence[int]) -> int:
        """Map a sequence's first `len(tokens)` positions, reusing cached
        prefix pages. Returns the number of tokens whose KV is already in
        the pool (always < len(tokens) so the caller computes at least the
        last token's logits). Raises NoFreeBlocksError leaving no state."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id} already has a block table")
        if type(tokens) is not list:
            # a list is read as it stands (the scheduler's is `submit`'s,
            # Python ints; numpy's integers hash and compare as theirs)
            tokens = [int(t) for t in tokens]
        if self.window_blocks or self.state_slots:
            # no prefix cache beside a window pool (the window table fills
            # a chunk at a time, `ensure_capacity`) nor beside recurrent
            # state (the sequence takes its slot here)
            need = self.blocks_needed(len(tokens))
            slot = int(bool(self.state_slots))
            if not self.can_allocate(need, n_slots=slot):
                raise NoFreeBlocksError(
                    f"cannot map sequence {seq_id}: {need} blocks "
                    f"({self.num_free()} free)"
                    + (f", a state slot ({len(self._sfree)} free)"
                       if slot else ""))
            self._tables[seq_id] = [self._alloc_block() for _ in range(need)]
            self._hashed[seq_id] = (0, 0)
            if self.window_blocks:
                self._wtables[seq_id] = []
                self._wreleased[seq_id] = 0
            if slot:
                self.take_slot(seq_id)
            return 0
        bs = self.block_size
        table: List[int] = []
        new_copies: List[Tuple[int, int]] = []
        cached = 0
        prev_h = before_last = 0
        try:
            # full-block prefix hits: share pages by refcount
            i, full_run = 0, True
            while i + bs <= len(tokens):
                h = _chain_hash(prev_h, tuple(tokens[i:i + bs]))
                blk = self._hash_to_block.get(h)
                if blk is None:
                    full_run = False
                    break
                self._incref(blk)
                table.append(blk)
                self.stats["prefix_hit_blocks"] += 1
                cached += bs
                before_last, prev_h = prev_h, h
                i += bs
            # partial hit on the next block (whether the chain ran out of
            # full-sized chunks or broke on content): copy-on-write. The
            # cached page holds another sequence's KV for these positions;
            # the matched leading tokens are identical, the page's tail is
            # garbage this sequence's causal mask never attends
            # (kv_pos <= tok_pos).
            if i < len(tokens):
                best = self._partial_match(prev_h, tokens[i:i + bs])
                if best is not None:
                    src, n_match = best
                    dst = self._alloc_block()
                    self._incref(src)          # pin until the copy executes
                    new_copies.append((src, dst))
                    table.append(dst)
                    self.stats["cow_copies"] += 1
                    cached += n_match
            # fresh pages for the rest
            while len(table) * bs < len(tokens):
                table.append(self._alloc_block())
            # the caller always recomputes at least the final prompt token
            # (cached is capped below), and that token's KV WRITE must not
            # land on a page other sequences can read: when the whole
            # prompt was full-block hits, demote the final hit to a
            # private copy-on-write page.
            if full_run and i >= len(tokens):
                src = table[-1]
                dst = self._alloc_block()
                new_copies.append((src, dst))   # table drop keeps src's ref
                table[-1] = dst
                # the private copy is not addressed yet: register_computed
                # goes on with it
                i, prev_h = i - bs, before_last
                self.stats["cow_copies"] += 1
        except NoFreeBlocksError:
            for src, _ in new_copies:
                self._decref(src)              # release the copy pins
            for b in table:
                self._decref(b)
            raise
        cached = min(cached, len(tokens) - 1)
        cached -= cached % self.hit_multiple
        self.stats["prefix_hit_tokens"] += cached
        self._pending_copies.extend(new_copies)
        self._tables[seq_id] = table
        self._hashed[seq_id] = (i // bs, prev_h)   # the pages hit
        return cached

    def _partial_match(self, prev_h: int,
                       rest: Sequence[int]) -> Optional[Tuple[int, int]]:
        """Longest cached full block sharing chain `prev_h` whose leading
        tokens match `rest`; None below 2 matched tokens (a COW page copy
        is not worth one token)."""
        rest = list(rest)
        best_blk, best_n = None, 1
        for h in self._children.get(prev_h, ()):
            chunk = self._hash_info[h][1]
            blk = self._hash_to_block.get(h)
            if blk is None or (blk not in self._refs
                               and blk not in self._cached_free):
                continue
            n = 0
            for a, b in zip(chunk, rest):
                if a != b:
                    break
                n += 1
            if n > best_n:
                best_blk, best_n = blk, n
        return (best_blk, best_n) if best_blk is not None else None

    def ensure_capacity(self, seq_id: int, num_tokens: int) -> int:
        """Grow a sequence's table to cover `num_tokens` positions (decode
        growth), allocating fresh pages as block boundaries are crossed.
        Pages reachable by other sequences are always FULL, so growth never
        writes into shared data. Returns pages added; raises
        NoFreeBlocksError (leaving the table unchanged) when the pool is
        exhausted — the scheduler's preemption trigger."""
        table = self._tables[seq_id]
        if self.window_blocks:
            need, wneed = self.growth(seq_id, num_tokens)
            if not self.can_allocate(need, wneed):
                raise NoFreeBlocksError(
                    f"cannot grow sequence {seq_id} by {need} + {wneed} "
                    f"window blocks ({self.num_free()} + "
                    f"{len(self._wfree)} free)")
            wtable = self._wtables[seq_id]
            for _ in range(wneed):
                wtable.append(self._wfree.pop())
            self.stats["window_allocs"] += wneed
        else:
            need = self.blocks_needed(num_tokens) - len(table)
            if need <= 0:
                return 0
            if not self.can_allocate(need):
                raise NoFreeBlocksError(
                    f"cannot grow sequence {seq_id} by {need} blocks "
                    f"({self.num_free()} free)")
        for _ in range(need):
            table.append(self._alloc_block())
        return need

    def release_behind(self, seq_id: int, num_computed: int) -> int:
        """Give back the window-pool pages of `seq_id` that no query to
        come can see: the next one sits at position `num_computed` or
        later and sees no key before `num_computed` - (window - 1), so a
        page whose last position lies before that goes back to the free
        list and its table entry reads -1. Returns pages released."""
        if not self.window_blocks or seq_id not in self._wtables:
            return 0
        wtable = self._wtables[seq_id]
        first = self._wreleased[seq_id]
        upto = min(max(0, num_computed - (self.window - 1))
                   // self.block_size, len(wtable))
        for p in range(first, upto):
            self._wfree.append(wtable[p])
            wtable[p] = -1
        if upto > first:
            self._wreleased[seq_id] = upto
            self.stats["window_released"] += upto - first
        return max(0, upto - first)

    def register_computed(self, seq_id: int, tokens: Sequence[int],
                          num_computed: int):
        """Content-address every full block covered by the first
        `num_computed` computed tokens of `tokens`, making them
        prefix-cache hits for future sequences. Goes on from the last page
        hashed for this sequence (a sequence's tokens only grow), so a call
        costs the pages that filled since the last one; every id below
        `num_computed` has to be known."""
        bs = self.block_size
        table = self._tables.get(seq_id)
        if table is None or self.window_blocks or self.state_slots:
            return
        bi, prev_h = self._hashed[seq_id]
        full = min(num_computed, len(tokens)) // bs
        if bi >= full:
            return
        for bi in range(bi, full):
            chunk = tuple(int(t) for t in tokens[bi * bs:(bi + 1) * bs])
            h = _chain_hash(prev_h, chunk)
            blk = table[bi]
            if h not in self._hash_to_block and blk not in self._block_hash:
                self._index_hash(h, blk, prev_h, chunk)
            prev_h = h
        self._hashed[seq_id] = (full, prev_h)

    def free_sequence(self, seq_id: int):
        table = self._tables.pop(seq_id, None)
        self._hashed.pop(seq_id, None)
        self._wreleased.pop(seq_id, None)
        self._wfree.extend(p for p in self._wtables.pop(seq_id, ())
                           if p >= 0)
        if seq_id in self._slots:
            self._sfree.append(self._slots.pop(seq_id))
        if not table:
            return
        if self._pending_copies:
            # drop not-yet-executed COW copies whose destination dies with
            # this table (cancel mid-chunked-prefill): the dst page is
            # about to be freed and may be handed to another sequence — a
            # stale device copy into it would corrupt that sequence's KV.
            # Destinations are private (ref==1, exactly one table), so
            # membership in this table identifies this sequence's pairs.
            dsts = set(table)
            kept: List[Tuple[int, int]] = []
            for src, dst in self._pending_copies:
                if dst in dsts:
                    self._decref(src)          # release the copy pin
                    self.stats["cow_purged"] += 1
                else:
                    kept.append((src, dst))
            self._pending_copies = kept
        for blk in table:
            self._decref(blk)

    def block_table(self, seq_id: int) -> List[int]:
        return list(self._tables[seq_id])

    def window_table(self, seq_id: int) -> List[int]:
        """The sequence's table over the window pool: as wide as what it
        has been grown to, -1 where a page was released."""
        return list(self._wtables[seq_id])

    def num_blocks_of(self, seq_id: int) -> int:
        return len(self._tables[seq_id])

    def has_sequence(self, seq_id: int) -> bool:
        return seq_id in self._tables

    def ref_count(self, blk: int) -> int:
        return self._refs.get(blk, 0)

    def take_copies(self) -> List[Tuple[int, int]]:
        """Drain the pending (src, dst) COW page copies; the engine must
        execute them on the device cache before its next step (the src
        pin is released here, so the copy must run before any further
        allocation can recycle the page)."""
        out, self._pending_copies = self._pending_copies, []
        for src, _ in out:
            self._decref(src)
        return out

    def prefix_chain(self,
                     tokens: Sequence[int]) -> List[Tuple[int, int]]:
        """Content-address the full-block prefix chain of `tokens`
        WITHOUT touching the pool: ``[(depth, chain_hash), ...]`` where
        ``depth`` is the token count covered through each full block.

        A pure function of the token list — sender, receiver and the
        fleet prefix index all compute the SAME pairs, so cross-replica
        page-pull requests can address pages content-wise without
        shipping raw tokens or re-hashing on the remote side. (The hash
        chains tuples of ints, which Python hashes deterministically —
        PYTHONHASHSEED only perturbs str/bytes — so the pairs agree
        across processes too.)"""
        tokens = [int(t) for t in tokens]
        bs = self.block_size
        out: List[Tuple[int, int]] = []
        prev_h, i = 0, 0
        while i + bs <= len(tokens):
            prev_h = _chain_hash(prev_h, tuple(tokens[i:i + bs]))
            i += bs
            out.append((i, prev_h))
        return out

    def _chain_live(self, chain_hash: int) -> Optional[int]:
        """Block id serving `chain_hash` right now (referenced or parked
        in the cached-free LRU), else None."""
        blk = self._hash_to_block.get(chain_hash)
        if blk is None or (blk not in self._refs
                           and blk not in self._cached_free):
            return None
        return blk

    def lookup_prefix(self, tokens: Sequence[int]) -> int:
        """How many leading tokens of `tokens` the pool could serve from
        the prefix cache right now (full-block chain hits only), WITHOUT
        allocating — the router's prefix-affinity signal. Capped at
        len(tokens)-1 like allocate_sequence's `cached`. Thin wrapper
        over :meth:`prefix_chain` + pool liveness."""
        tokens = [int(t) for t in tokens]
        n = 0
        for depth, h in self.prefix_chain(tokens):
            if self._chain_live(h) is None:
                break
            n = depth
        return min(n, max(len(tokens) - 1, 0))

    def chain_blocks(self,
                     chain: Sequence[Tuple[int, int]]) -> Optional[List[int]]:
        """Resolve a :meth:`prefix_chain` to live block ids, or None when
        any link is missing (pages partially evicted — this pool cannot
        serve the chain and a sender must decline the page pull)."""
        out: List[int] = []
        for _, h in chain:
            blk = self._chain_live(h)
            if blk is None:
                return None
            out.append(blk)
        return out

    def adopt_page(self, chain_hash: int, prev_hash: int,
                   chunk: Sequence[int]) -> Optional[int]:
        """Park an externally computed (migrated) full page in the prefix
        cache: take a free block, register the chain hash, and leave it
        in the cached-free LRU so the next ``allocate_sequence`` revives
        it like any freed-but-cached page — and allocation pressure can
        reclaim it (migrated pages are an optimization, never pinned
        state). Returns the block id the caller must fill on device, or
        None when the hash is already live here (nothing to write).
        Raises NoFreeBlocksError when every block is referenced."""
        if self._chain_live(chain_hash) is not None:
            return None
        blk = self._take_free()
        self._drop_hash(blk)       # fresh-list blocks may carry no hash;
        #                            reclaim path already dropped theirs
        self._index_hash(chain_hash, blk, int(prev_hash),
                         tuple(int(t) for t in chunk))
        self._cached_free[blk] = None
        self.stats["adopted_pages"] += 1
        return blk

    def evict_hashes(self, hashes: Sequence[int]) -> int:
        """Drop prefix-cache entries by chain hash (migrated pages found
        bad at confirm time): parked pages return to the raw free list;
        pages still referenced by live sequences only lose their hash
        (the data stays until their refs drain). Returns entries
        dropped."""
        n = 0
        for h in list(hashes):
            blk = self._hash_to_block.get(h)
            if blk is None:
                continue
            self._drop_hash(blk)
            if blk in self._cached_free:
                del self._cached_free[blk]
                self._free.append(blk)
            n += 1
        return n
