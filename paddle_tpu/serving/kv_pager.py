"""Paged KV cache with prefix sharing — the serving engine's block-table
memory manager (ISSUE r20 tentpole).

The slot engine (serving/engine.py) reserves one full [max_len] KV row
per slot — the serving-layer incarnation of the naive per-tensor
reservation the reference's L1 BuddyAllocator exists to kill (PAPER.md
§L1), and exactly the waste the r17 census prices in its `kv_cache`
category. This module replaces the per-slot rows with PAGES:

- `BlockPool` — host-side free-list + refcount accounting over ONE
  device-resident pool per layer per k/v ([n_blocks, nh, block_size,
  dh] persistable vars). Physical block 0 is the reserved NULL block:
  idle tick slots are steered to write there, and no live block table
  ever maps it.
- `BlockTable` — a request's logical→physical mapping: logical block j
  (token positions [j*block_size, (j+1)*block_size)) lives in physical
  block `blocks[j]`. Tables replace slot rows; a request holds exactly
  ceil((prompt+max_new)/block_size) blocks instead of max_len tokens.
- `RadixPrefixIndex` — block-granular prefix sharing: full
  `block_size`-token prompt blocks are registered (keyed by their token
  content) the moment their last row is written; a later request whose
  prompt starts with the same tokens maps its LEADING table entries to
  the SAME physical blocks (refcounted, zero prefill ticks for the
  shared span). Sharing is capped block-aligned at len(prompt)-1 so a
  write can NEVER land in a shared block and at least one prompt token
  remains to feed the tick. Cached blocks persist after their request
  completes (the index holds its own ref) and are evicted LRU
  LEAF-FIRST under pool pressure — evicting a mid-chain node would
  orphan its descendants' match path.
- Copy-on-write at the divergence block: `KVPager.fork` (beam search's
  hypothesis split) shares all fully-written blocks by refcount and
  EAGERLY copies the one partially-written block — the fork point — so
  each branch owns its divergence block before it writes there.
- `PagedKVEngine` — the ContinuousBatchingEngine subclass that decodes
  through all of the above: same scheduler/tick loop, but admission
  acquires a block table (head-of-line wait under pool pressure, with
  LRU eviction of cached prefixes), prefill SKIPS shared positions
  (compute is deterministic — the shared blocks hold byte-identical
  K/V, which is why decode is token-identical to the slot engine), and
  the compiled tick is `transformer_lm_paged_decode_tick` (rows written
  in place, the pool read through the block table by
  `paged_decode_attention`: fusion/paged_attention.py).
- `paged_beam_search` — beam decode over the paged engine: hypotheses
  share their common prefix physically (block refcounts), forks CoW the
  divergence block, and the per-tick top-k log-probs from the compiled
  tick drive host-side hypothesis selection.

Capacity math: at fixed pool bytes a
request pins ceil(L/block_size) blocks instead of max_len tokens, so
short/long-tail mixes admit ~max_len/L× more concurrency, and shared
prefixes reduce the marginal request to its PRIVATE blocks only.
Accounting is exact by construction: used + free == n_blocks - 1 (the
null block is neither) at every instant, and the census `kv_cache`
category (pool bytes) splits into the reserved/used watermark pair
(observability/memory.py channels `kv_cache_bytes` /
`kv_cache_used_bytes`).

Two-tier paging (ISSUE r23 tentpole): `PagedKVEngine(host_tier=
HostTierConfig(...))` extends the hierarchy one level down. Requests
keep being ADMITTED when the device pool is dry — they hold a tick
slot in a SUSPENDED state (zero bytes on either tier until they have
ticked) while the resident set decodes; a resident request's private
blocks can be EVICTED to the pinned host pool (d2h on the shared
transfer stream, overlapped with the next ticks — jax arrays are
immutable, so the snapshot the stream reads stays consistent after the
device blocks are rehandled) and PREFETCHED back `prefetch_distance`
ticks ahead of the projected resume (`offload.prefetch_issue_tick`,
the same helper `lint_program --offload` checks). Shared prefix-index
blocks are pinned on device — they are the highest-fanout bytes.
Per-slot decode is independent and deterministic, so suspend/resume
changes WHICH slots tick, never what any slot computes: two-tier
decode is token-identical to device-only decode (asserted by
tests/test_offload.py). The two-pool
accounting identity extends exactly: used_dev + used_host + free_dev +
free_host == (n_blocks - 1) + host_blocks (`KVPager.check_two_tier`).

Two kinds of attention layer (ISSUE 45): a model with sliding-window
layers keeps those layers' K/V in a SECOND pool behind a second table a
request (`BlockTable.window_blocks`): a block is mapped when a tick first
writes it (`KVPager.map_window`) and released once every position of it
has slid out of the window (`KVPager.slide_window`), so a request holds a
bounded number of window blocks whatever its length; an index node keeps
its window block only as part of a registered span's window TAIL, and a
prefix hit is cut where the tail is gone (docs/serving.md, "the window
pool"). Both pools' accounting is exact (`KVPager.check_window`).

Ownership verification (ISSUE r24): every mutation this module makes
is modeled declaratively in `framework/ownership.py` — the
depth-bounded model checker proves the protocol's invariants over all
op interleavings at small scope, and with `PTPU_KV_SANITIZE=1` the
runtime shadow (`serving/sanitizer.py`, attached in
`KVPager.__init__`) mirrors each real mutation into that model and
raises the named diagnostic on any divergence.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.enforce import InvalidArgumentError, enforce
from ..framework import offload as _offload
from ..framework.offload import HostTierConfig
from ..framework.ownership import check_span_snapshot, check_window_read
from ..observability import memory as _obs_memory
from ..observability import tracing as _tracing
from .engine import (ContinuousBatchingEngine, GenRequest, _ENGINE_SEQ,
                     _feed_arrays)


class BlockPool:
    """Free-list + refcount accounting over the device block pool.

    Host-side only — the device arrays are the engine's persistable
    pool vars; this class decides WHICH physical block holds what.
    Block 0 is reserved as the null block (idle-slot write target): it
    is never on the free list and never allocated. Invariant, checked
    on demand via `check()`: n_used + n_free == n_blocks - 1, and a
    block is on the free list iff its refcount is 0."""

    def __init__(self, n_blocks: int, block_size: int):
        enforce(n_blocks >= 2,
                "pool needs at least 2 blocks (block 0 is the reserved "
                "null block)", exc=InvalidArgumentError)
        enforce(block_size >= 1, "block_size must be >= 1",
                exc=InvalidArgumentError)
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self._free = list(range(n_blocks - 1, 0, -1))   # LIFO: reuse hot
        self._ref = [0] * n_blocks                      # ref[0] stays 0

    def alloc(self) -> Optional[int]:
        """Take a free block (refcount 1); None when the pool is dry —
        the caller decides whether to evict or wait."""
        if not self._free:
            return None
        b = self._free.pop()
        self._ref[b] = 1
        return b

    def share(self, block: int):
        """One more holder of an allocated block (prefix share, beam
        fork, or the radix index's own retention ref)."""
        enforce(0 < block < self.n_blocks and self._ref[block] > 0,
                f"share of unallocated block {block}",
                exc=InvalidArgumentError)
        self._ref[block] += 1

    def release(self, block: int) -> bool:
        """Drop one ref; True when that freed the block (refcount hit
        0 and it returned to the free list)."""
        enforce(0 < block < self.n_blocks and self._ref[block] > 0,
                f"release of unallocated block {block}",
                exc=InvalidArgumentError)
        self._ref[block] -= 1
        if self._ref[block] == 0:
            self._free.append(block)
            return True
        return False

    def refcount(self, block: int) -> int:
        return self._ref[block]

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_blocks - 1 - len(self._free)

    def check(self):
        """Assert the accounting identity (tests + CI reconciliation):
        used + free == n_blocks - 1, free iff refcount 0."""
        enforce(self.n_used + self.n_free == self.n_blocks - 1,
                f"pool accounting broken: used({self.n_used}) + "
                f"free({self.n_free}) != {self.n_blocks - 1}",
                exc=InvalidArgumentError)
        free = set(self._free)
        enforce(len(free) == len(self._free),
                "pool free list holds duplicates",
                exc=InvalidArgumentError)
        for b in range(1, self.n_blocks):
            enforce((self._ref[b] == 0) == (b in free),
                    f"block {b}: refcount {self._ref[b]} vs free-list "
                    f"membership {b in free}", exc=InvalidArgumentError)
        enforce(self._ref[0] == 0 and 0 not in free,
                "null block 0 must stay unallocated and off the free "
                "list", exc=InvalidArgumentError)


class BlockTable:
    """One request's logical→physical block mapping. `blocks[j]` is the
    physical home of token positions [j*block_size, (j+1)*block_size);
    the leading `n_shared` entries came from the prefix index (read-only
    to this request — writes start at `shared_len`)."""

    __slots__ = ("blocks", "n_shared", "shared_len", "snapshot",
                 "snapshot_write", "window_blocks", "window_lo")

    def __init__(self, blocks: List[int], n_shared: int = 0,
                 shared_len: int = 0):
        self.blocks = list(blocks)
        self.n_shared = int(n_shared)
        self.shared_len = int(shared_len)
        #: the snapshot-pool entry the request's first chunk starts from
        #: (pinned from admission until that chunk ran), and (entry, logical
        #: block) of the snapshot a lane of the tick in flight is writing
        self.snapshot: Optional[int] = None
        self.snapshot_write: Optional[Tuple[int, int]] = None
        #: the SECOND table, of a model with sliding-window layers (None
        #: without): logical block j -> its block of the window pool, 0
        #: where it is not mapped (released behind the window, never
        #: written yet, or below a shared span's tail); `window_lo` is the
        #: first logical block that may still be mapped
        self.window_blocks: Optional[List[int]] = None
        self.window_lo = 0

    @property
    def window_held(self) -> int:
        """Window-pool blocks this table maps right now."""
        wb = self.window_blocks
        return 0 if wb is None else sum(1 for b in wb[self.window_lo:] if b)

    def __len__(self):
        return len(self.blocks)

    def __repr__(self):
        return (f"BlockTable(blocks={self.blocks}, "
                f"n_shared={self.n_shared})")


class _RadixNode:
    __slots__ = ("key", "block", "children", "parent", "last_used", "snap",
                 "wblock")

    def __init__(self, key, block, parent):
        self.key = key              # tuple of block_size token ids
        self.block = block          # physical block holding their K/V
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.parent = parent
        self.last_used = 0
        self.snap: Optional[int] = None   # its entry of the snapshot pool
        self.wblock: Optional[int] = None  # its block of the window pool


class RadixPrefixIndex:
    """Block-granular prompt-prefix index: a radix tree whose edges are
    FULL blocks of `block_size` tokens (a partial block is never
    sharable — its tail would be another request's garbage). Each node
    pins its physical block with one index-owned refcount, so cached
    prefixes survive their originating request until evicted. Matching
    walks children by exact token-tuple key; eviction is LRU over LEAF
    nodes only (a mid-chain eviction would break descendants' match
    paths while they still pin device blocks)."""

    def __init__(self, block_size: int):
        self.block_size = int(block_size)
        self.root = _RadixNode((), None, None)
        self._clock = 0
        self.n_cached = 0
        #: called with every node that leaves the tree (the pager voids the
        #: node's state snapshot)
        self.on_evict: Optional[Callable[[_RadixNode], None]] = None

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _keys(self, prompt: Sequence[int], n: int) -> List[tuple]:
        bs = self.block_size
        return [tuple(prompt[j * bs:(j + 1) * bs]) for j in range(n)]

    def match(self, prompt: Sequence[int]) -> List[_RadixNode]:
        """Longest chain of cached FULL blocks prefixing `prompt`
        (match order = logical block order). Bumps LRU clocks."""
        node, out = self.root, []
        for key in self._keys(prompt, len(prompt) // self.block_size):
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = self._tick()
            out.append(child)
            node = child
        return out

    def register(self, prompt: Sequence[int], logical_block: int,
                 phys: int, pool: BlockPool,
                 note: Optional[Callable[[_RadixNode], None]] = None) -> bool:
        """Offer block `logical_block` of `prompt` (physically `phys`,
        just fully written) to the cache. No-ops when the content chain
        already exists (a concurrent request filled the same prefix
        first — the existing copy stays canonical) or when an ancestor
        chain node is missing (evicted mid-flight — registering would
        orphan the new node's match path). On success the index takes
        its OWN ref on `phys`, so the block outlives its request. `note` is
        called with the block's node, new or cached before (the pager hangs
        the node's window-pool block on it)."""
        node = self.root
        keys = self._keys(prompt, logical_block + 1)
        for j, key in enumerate(keys):
            child = node.children.get(key)
            if child is None:
                if j < logical_block:
                    return False            # broken ancestor chain
                child = _RadixNode(key, phys, node)
                node.children[key] = child
                pool.share(phys)            # the index's retention ref
                self.n_cached += 1
                child.last_used = self._tick()
                if note is not None:
                    note(child)
                return True
            child.last_used = self._tick()
            node = child
        if note is not None and keys:
            note(node)
        return False                        # full chain already cached

    def evict_one(self, pool: BlockPool) -> bool:
        """Evict the least-recently-used LEAF node (zero children),
        dropping the index's ref on its block — the block frees iff no
        live table still holds it. False when the index is empty."""
        victim = None
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            elif victim is None or n.last_used < victim.last_used:
                victim = n
        if victim is None:
            return False
        del victim.parent.children[victim.key]
        pool.release(victim.block)
        self.n_cached -= 1
        if self.on_evict is not None:
            self.on_evict(victim)
        return True

    def node_of(self, prompt: Sequence[int],
                logical_block: int) -> Optional[_RadixNode]:
        """The node of block `logical_block` of `prompt`'s chain, if the
        whole chain is cached (no LRU clock moves)."""
        node = self.root
        for key in self._keys(prompt, logical_block + 1):
            node = node.children.get(key)
            if node is None:
                return None
        return node

    def evict_all(self, pool: BlockPool) -> int:
        n = 0
        while self.evict_one(pool):
            n += 1
        return n


class SpillRecord:
    """One suspended request's host-tier residency: which LOGICAL table
    entries were spilled (ascending), and how many host blocks they
    hold. The physical device ids they came from are dead the moment
    the spill releases them — only the engine's host buffers (keyed by
    the same ascending order) carry the content."""

    __slots__ = ("spilled", "n_blocks")

    def __init__(self, spilled: List[int], n_blocks: int):
        self.spilled = list(spilled)     # logical indices, ascending
        self.n_blocks = int(n_blocks)    # len(table.blocks) at spill


class KVPager:
    """The paged-KV policy engine: owns the BlockPool and the
    RadixPrefixIndex, makes the admission / share / CoW / release /
    eviction decisions, and keeps the counters the metrics registry
    exposes. Device bytes are the engine's; this is the brain.

    With `host_tier=HostTierConfig(...)` the pager also arbitrates the
    SECOND tier: `evict_table_to_host` trades a resident table's
    private device blocks for host-block capacity, and
    `reload_table_from_host` trades back. The pager still never
    touches bytes — the engine moves them on the transfer stream; this
    ledger only guarantees the two-pool identity
    used_dev + used_host + free_dev + free_host == total."""

    def __init__(self, n_blocks: int, block_size: int,
                 prefix_sharing: bool = True,
                 host_tier: Optional[HostTierConfig] = None,
                 block_state: bool = False, n_snapshots: int = 0,
                 window: int = 0, n_window_blocks: int = 0):
        self.block_size = int(block_size)
        self.prefix_sharing = bool(prefix_sharing)
        #: the model keeps a per-request STATE beside its per-token rows
        #: (conv layers): every block then carries a snapshot of the state
        #: after its last position, written by the tick that fills it.
        #: `_snap[b]` says block b's is valid; it is void once the block
        #: is handed out again, and a prefix hit is served only up to a
        #: block that has one (the request resumes from it).
        self.block_state = bool(block_state)
        self._snap = np.zeros(n_blocks, bool)
        self.state_restores = 0         # admissions resumed from a snapshot
        self.state_snapshots = 0        # block snapshots written
        self.pool = BlockPool(n_blocks, block_size)
        self.index = RadixPrefixIndex(block_size)
        #: ... or, where a copy of the state is megabytes (state-space
        #: layers), a POOL of `n_snapshots` snapshots, far fewer than
        #: blocks: an index node may hold an entry (`_RadixNode.snap`),
        #: written by the lane whose chunk crosses the end of a prompt's
        #: last whole block. A prefix hit is truncated to the deepest node
        #: that holds one (the state-space layers would recompute the
        #: positions beyond it anyway); an entry is taken least recently
        #: used among the unpinned, and void once its node leaves the index.
        self.n_snapshots = int(n_snapshots)
        self._snap_node: List[Optional[_RadixNode]] = [None] * self.n_snapshots
        self._snap_used = [0] * self.n_snapshots     # LRU clock an entry
        self._snap_pins = [0] * self.n_snapshots     # admitted, not yet read
        self._snap_clock = 0
        # what the eviction order reads beside the clock (`_snapshot_rank`):
        # the clock of an entry's last RESTORE (0: none yet) and the logical
        # block its state stands behind
        self._snap_restored = [0] * self.n_snapshots
        self._snap_depth = [0] * self.n_snapshots
        self.snapshot_evictions = 0     # valid entries taken for another
        self.hits_truncated = 0         # hits cut to a shallower snapshot
        #: ... or, where some layers attend a sliding WINDOW of `window`
        #: positions, a second pool for those layers' K/V and a second table
        #: a request (`BlockTable.window_blocks`): a block is mapped from
        #: the tick that first writes it (`map_window`) until every
        #: position of it has slid out of the window (`slide_window`), so a
        #: request holds a bounded number whatever its length. An index
        #: node holds its window block only as one of the last
        #: `tail_nodes` blocks of a prompt as it was registered (the
        #: span's window TAIL); a prefix hit is cut to the deepest node
        #: whose tail is resident, and a tail is evicted with its node, or
        #: alone (least recently used) when the window pool runs dry.
        self.window = int(window)
        self.wpool = BlockPool(n_window_blocks, block_size) \
            if self.window else None
        self.tail_nodes = -(-self.window // self.block_size) + 1 \
            if self.window else 0
        self._tails: Dict[int, _RadixNode] = {}   # id(node) -> node w/ wblock
        self.window_blocks_released = 0  # slid out of a request's window
        self.window_tail_lookups = 0     # admissions that matched a span
        self.window_tail_hits = 0        # ... and kept the whole of it
        self.window_tail_evictions = 0   # tails dropped, node kept
        #: window blocks a request held, counted at each of its commits
        #: (index = blocks, value = commits)
        self.window_blocks_held = np.zeros(64, np.int64)
        if self.n_snapshots or self.window:
            self.index.on_evict = self._node_evicted
        self.host_tier = host_tier
        self.host_blocks_used = 0
        # -- counters (ptpu_engine_* gauges read these) --
        self.n_admitted = 0
        self.prefix_hits = 0            # admissions with shared_len > 0
        self.shared_blocks_total = 0    # table entries served by the index
        self.blocks_allocated_total = 0
        self.evictions = 0
        self.cow_copies = 0
        self.rolled_back_blocks = 0     # speculative-decode rejected spans
        self.host_evictions = 0         # blocks spilled device -> host
        self.host_reloads = 0           # spilled blocks reloaded h -> d
        self.host_prefetch_hits = 0     # resumes whose h2d had landed
        self.host_prefetch_misses = 0   # resumes that waited on the h2d
        # shadow-state sanitizer (PTPU_KV_SANITIZE=1): mirrors every
        # pool/pager mutation into the framework/ownership.py model and
        # raises the named diagnostic on divergence; None when off —
        # nothing is wrapped, so the off path costs nothing per op
        from . import sanitizer as _sanitizer
        self.sanitizer = _sanitizer.attach(self)

    # -- admission --------------------------------------------------------
    def blocks_needed(self, length: int) -> int:
        return -(-int(length) // self.block_size)

    def try_admit(self, prompt: Sequence[int],
                  need_len: int) -> Optional[BlockTable]:
        """Acquire a block table spanning `need_len` token positions for
        `prompt`, serving the leading blocks from the prefix cache when
        possible. None when the pool (after LRU eviction of cached
        prefixes) cannot cover the private remainder — the scheduler
        leaves the request at the head of the queue (no starvation).

        The shared span is capped at block-aligned len(prompt)-1: a
        request always keeps >= 1 prompt position to feed through the
        tick, and its first write lands in its first PRIVATE block —
        writes can never target shared blocks."""
        n_logical = self.blocks_needed(need_len)
        shared_nodes: List[_RadixNode] = []
        if self.prefix_sharing:
            shared_nodes = self.index.match(prompt)
        max_shared = (len(prompt) - 1) // self.block_size
        shared_nodes = shared_nodes[:min(max_shared, n_logical)]
        if self.block_state and shared_nodes:
            # the request resumes from the snapshot of the span's last block:
            # every indexed block has one (`note_block_filled` offers none
            # without, and a block handed out again left the index first)
            enforce(self._snap[shared_nodes[-1].block],
                    f"block {shared_nodes[-1].block} is in the prefix index "
                    f"without a state snapshot")
            self.state_restores += 1
        entry = None
        if self.n_snapshots and shared_nodes:
            # the span ends at the deepest node that holds a snapshot
            matched = len(shared_nodes)
            while shared_nodes and shared_nodes[-1].snap is None:
                shared_nodes.pop()
            self.hits_truncated += len(shared_nodes) < matched
            if shared_nodes:
                entry = shared_nodes[-1].snap
                # kin to `kv-state-snapshot-missing`: a span is never handed
                # out past its snapshot
                holder = self._snap_node[entry]
                check_span_snapshot([n.block for n in shared_nodes],
                                    holder and holder.block, "try_admit")
                self._snap_pins[entry] += 1
        wtail: List[int] = []       # the span's window tail, by block
        if self.window and shared_nodes:
            # the span ends at the deepest node whose window tail is resident
            # (`hits_truncated`, as for a missing snapshot): the request's
            # first chunk reads the last window - 1 positions of the span
            matched = len(shared_nodes)
            self.window_tail_lookups += 1
            while shared_nodes and not all(
                    n.wblock for n in self._tail_of(shared_nodes)):
                shared_nodes.pop()
            self.hits_truncated += len(shared_nodes) < matched
            self.window_tail_hits += len(shared_nodes) == matched
            wtail = [n.wblock for n in self._tail_of(shared_nodes)]
        # pin the matched blocks FIRST: eviction under pressure below
        # may drop their index nodes, but a pinned block cannot free
        blocks = []
        for node in shared_nodes:
            self.pool.share(node.block)
            blocks.append(node.block)
        for held in wtail:
            self.wpool.share(held)
        need_new = n_logical - len(shared_nodes)
        for _ in range(need_new):
            b = self._alloc_or_evict()
            if b is None:                    # rollback, stay pending
                for held in blocks:
                    self.pool.release(held)
                for held in wtail:
                    self.wpool.release(held)
                if entry is not None:
                    self._snap_pins[entry] -= 1
                return None
            blocks.append(b)
        n_shared = len(shared_nodes)
        self.n_admitted += 1
        self.blocks_allocated_total += need_new
        if n_shared:
            self.prefix_hits += 1
            self.shared_blocks_total += n_shared
        table = BlockTable(blocks, n_shared, n_shared * self.block_size)
        if self.window:
            table.window_blocks = [0] * n_logical
            table.window_lo = n_shared - len(wtail)
            table.window_blocks[table.window_lo:n_shared] = wtail
            if n_shared:
                # the rule a span is handed out under: its first chunk's
                # window read finds every block it spans
                check_window_read(table.window_blocks,
                                  self.window_first(table.shared_len),
                                  n_shared - 1, "try_admit")
        if entry is not None:
            table.snapshot = entry
            if self._snap_node[entry] is not None:   # else: evicted just now
                self._touch_snapshot(entry)
                self._snap_restored[entry] = self._snap_clock
            self.state_restores += 1
        return table

    # -- the snapshot pool ------------------------------------------------
    @property
    def snapshots_valid(self) -> int:
        """Entries that hold an index node's snapshot."""
        return sum(n is not None for n in self._snap_node)

    def _touch_snapshot(self, entry: int):
        self._snap_clock += 1
        self._snap_used[entry] = self._snap_clock

    def _node_evicted(self, node: _RadixNode):
        """`node` left the index: its snapshot is void (an admitted request
        that has yet to read it keeps its pin, and the entry with it)."""
        if node.snap is not None:
            self._snap_node[node.snap] = None
            self._snap_used[node.snap] = 0
            node.snap = None
        if node.wblock is not None:     # its window tail goes with it
            self._drop_tail(node)

    def snapshot_read(self, table: BlockTable):
        """The table's first chunk ran (or never will): unpin the entry it
        was admitted onto."""
        if table.snapshot is not None:
            self._snap_pins[table.snapshot] -= 1
            table.snapshot = None

    def take_snapshot_entry(self, table: BlockTable,
                            logical_block: int) -> Optional[int]:
        """An entry for the state after block `logical_block` of `table`'s
        prompt, which a lane of the tick being filled will write: the least
        recently used unpinned one, its old holder's snapshot void from
        now. Pinned until `snapshot_written`. None when every entry is
        pinned (the lane writes none)."""
        free = [e for e in range(self.n_snapshots) if not self._snap_pins[e]]
        if not free:
            return None
        entry = min(free, key=self._snapshot_rank)
        node = self._snap_node[entry]
        if node is not None:
            node.snap = None
            self._snap_node[entry] = None
            self.snapshot_evictions += 1
        self._snap_pins[entry] += 1
        table.snapshot_write = (entry, logical_block)
        return entry

    #: an entry counts as PROVEN while its last restore lies within this many
    #: pool-fuls of the snapshot clock (a tick of it a write or a restore)
    PROVEN_FOR = 16

    def _snapshot_rank(self, entry: int):
        """What `take_snapshot_entry` evicts first (the least): an entry
        that holds nothing; then an entry no request has restored from
        lately (the DEEPEST first: a state behind a request's own turn serves
        that one prompt, the state behind a shorter prefix of it every prompt
        that starts so; the least recently used among equals); a PROVEN entry
        (restored from within `PROVEN_FOR` pool-fuls of writes and restores)
        only when nothing else is left, the least recently used first. Plain
        LRU let the one-off snapshot every request writes at its own prompt's
        end push out a shared context's that was not asked for for a pool-ful
        of requests, and a context whose snapshot is gone is prefilled WHOLE
        by every later request that starts from it (the hit is cut to the
        deepest node that holds a snapshot, and a request writes one at its
        own end alone): PERF.md section 6, PR 61."""
        if self._snap_node[entry] is None:
            return (0, 0, self._snap_used[entry])
        last = self._snap_restored[entry]
        if last and self._snap_clock - last <= self.PROVEN_FOR * self.n_snapshots:
            return (2, 0, self._snap_used[entry])
        return (1, -self._snap_depth[entry], self._snap_used[entry])

    def snapshot_written(self, table: BlockTable, prompt: Sequence[int]):
        """The tick that wrote `table.snapshot_write` is done: hand the
        entry to the index node of that block (registered by
        `note_block_filled` just before, or an earlier request's), unless
        the chain is broken or the node already holds one."""
        entry, logical_block = table.snapshot_write
        table.snapshot_write = None
        self._snap_pins[entry] -= 1
        self.state_snapshots += 1
        node = self.index.node_of(prompt, logical_block) \
            if self.prefix_sharing else None
        if node is None or node.snap is not None:
            self._snap_used[entry] = 0          # free again, first to go
            return
        node.snap = entry
        self._snap_node[entry] = node
        self._snap_restored[entry] = 0
        self._snap_depth[entry] = logical_block
        self._touch_snapshot(entry)

    # -- the window pool --------------------------------------------------
    def window_first(self, pos: int) -> int:
        """The first logical block a window read at position `pos` spans."""
        return max(pos - (self.window - 1), 0) // self.block_size

    def _tail_of(self, nodes: List[_RadixNode]) -> List[_RadixNode]:
        """The nodes of a span of `len(nodes)` blocks whose window blocks
        the position after the span still attends."""
        return nodes[self.window_first(len(nodes) * self.block_size):]

    def window_bound(self, chunk: int) -> int:
        """The most window blocks one request holds at a time, whatever its
        length, with `chunk` prompt tokens a tick."""
        return -(-(self.window + int(chunk)) // self.block_size) + 1

    def _node_holds_tail(self, node: _RadixNode, wblock: int):
        """The index takes its own ref on `wblock` for `node`."""
        self.wpool.share(wblock)
        node.wblock = wblock
        self._tails[id(node)] = node

    def _drop_tail(self, node: _RadixNode):
        self.wpool.release(node.wblock)
        node.wblock = None
        del self._tails[id(node)]

    def _alloc_window(self) -> int:
        """A block of the window pool; under pressure the least recently
        used tail goes, its node stays (deeper hits are cut, never wrong).
        The engine sizes the pool so that every slot's bound fits beside
        nothing else: this cannot come up dry."""
        while True:
            b = self.wpool.alloc()
            if b is not None:
                return b
            enforce(self._tails,
                    f"window pool exhausted: {self.wpool.n_blocks - 1} blocks "
                    f"all held by live requests (the engine sizes it to "
                    f"n_slots x the per-request bound)",
                    exc=InvalidArgumentError)
            self._drop_tail(min(self._tails.values(),
                                key=lambda n: n.last_used))
            self.window_tail_evictions += 1

    def map_window(self, table: BlockTable, pos: int, n: int = 1):
        """The tick being filled writes positions pos..pos+n-1 of `table`'s
        request into the window layers: map their logical blocks (a block
        already mapped stays), then hold the read to the window's rule."""
        wb = table.window_blocks
        last = (pos + n - 1) // self.block_size
        for j in range(pos // self.block_size, last + 1):
            if not wb[j]:
                wb[j] = self._alloc_window()
        check_window_read(wb, self.window_first(pos), last, "map_window")

    def slide_window(self, table: BlockTable, fed: int):
        """Positions below `fed` are committed: every window block wholly
        below position fed - 1 - (window - 1) goes back to the pool (the
        index keeps a tail's block alive), and what the request still holds
        is counted."""
        wb = table.window_blocks
        if wb is None:
            return
        keep = max(fed - self.window, 0) // self.block_size
        for j in range(table.window_lo, min(keep, len(wb))):
            if wb[j]:
                self.wpool.release(wb[j])
                wb[j] = 0
                self.window_blocks_released += 1
        table.window_lo = max(table.window_lo, min(keep, len(wb)))
        held = table.window_held
        self.window_blocks_held[min(held, len(self.window_blocks_held) - 1)] \
            += 1

    def check_window(self):
        """The window pool's accounting identity (`BlockPool.check`: used +
        free == n - 1, free iff refcount 0); nothing without a window."""
        if self.wpool is not None:
            self.wpool.check()

    def _alloc_or_evict(self) -> Optional[int]:
        while True:
            b = self.pool.alloc()
            if b is not None:
                self._snap[b] = False    # its last holder's state is void
                return b
            if not self.index.evict_one(self.pool):
                return None
            self.evictions += 1

    # -- lifecycle --------------------------------------------------------
    def note_block_filled(self, table: BlockTable, logical_block: int,
                          prompt: Sequence[int], snapshot: bool = False):
        """Block `logical_block` of the request just received its last
        row. If it is a FULL prompt block (generated tokens are not
        shareable prefix — they differ per request even for equal
        prompts under different max_new/eos) and not itself served from
        the index, offer it to the prefix cache NOW: a request arriving
        mid-prefill of its twin already shares the finished span.
        `snapshot`: the tick that filled it also wrote its state snapshot
        (a prefill lane of a model with conv layers; a block a DECODE row
        completes has none, `_note_position_written`)."""
        if snapshot and logical_block >= table.n_shared:
            self._snap[table.blocks[logical_block]] = True
            self.state_snapshots += 1
        if not self.prefix_sharing or logical_block < table.n_shared:
            return
        if (logical_block + 1) * self.block_size > len(prompt):
            return
        if self.block_state and not self._snap[table.blocks[logical_block]]:
            return          # nothing to resume from: not offered
        note = None
        if self.window and logical_block >= \
                len(prompt) // self.block_size - self.tail_nodes:
            # one of the span's last blocks as it is registered: its node
            # holds the window block too (a node cached before that lost
            # its tail gets this request's, the same bytes)
            wblock = table.window_blocks[logical_block]

            def note(node):
                if node.wblock is None and wblock:
                    self._node_holds_tail(node, wblock)
        self.index.register(prompt, logical_block,
                            table.blocks[logical_block], self.pool, note)

    def fork(self, table: BlockTable, written_len: int,
             copy_block: Callable[[int, int], None]) -> BlockTable:
        """Split a hypothesis (beam search): the fork shares every FULLY
        written block by refcount, COPY-ON-WRITES the one partially
        written block (the divergence block — `copy_block(src, dst)`
        moves its device bytes), and takes fresh private blocks for the
        not-yet-written remainder. Raises when the pool cannot cover
        the fork even after eviction."""
        enforce(not self.window,
                "fork walks the block table alone: a model with window "
                "layers keeps a second table it would neither share nor copy",
                exc=InvalidArgumentError)
        n_full, rem = divmod(int(written_len), self.block_size)
        blocks: List[int] = []
        try:
            for j, b in enumerate(table.blocks):
                if j < n_full:
                    self.pool.share(b)
                    blocks.append(b)
                    continue
                nb = self._alloc_or_evict()
                if nb is None:
                    raise InvalidArgumentError(
                        f"block pool exhausted forking at block {j} "
                        f"({self.pool.n_free} free of "
                        f"{self.pool.n_blocks - 1})")
                if j == n_full and rem:
                    copy_block(b, nb)        # CoW at the divergence block
                    self.cow_copies += 1
                blocks.append(nb)
                self.blocks_allocated_total += 1
        except Exception:
            for held in blocks:
                self.pool.release(held)
            raise
        return BlockTable(blocks, table.n_shared, table.shared_len)

    def release(self, table: BlockTable):
        """Drop the table's ref on every LIVE mapping (completion or
        fork retirement). Blocks the prefix index also holds stay
        resident (cached) until evicted; everything else frees. Dead
        (zeroed) mappings — a table released while its content is
        host-resident, the drain/shutdown path — are skipped: their
        device refs were already traded for the host charge at spill
        time (the caller refunds that via `refund_host_charge`)."""
        for b in table.blocks:
            if b:
                self.pool.release(b)
        table.blocks = []
        if table.window_blocks is not None:
            for b in table.window_blocks[table.window_lo:]:
                if b:
                    self.wpool.release(b)
            table.window_blocks = None
        self.snapshot_read(table)
        if table.snapshot_write is not None:     # its tick never committed
            self._snap_pins[table.snapshot_write[0]] -= 1
            table.snapshot_write = None

    def rollback(self, table: BlockTable, keep_len: int,
                 written_len: int) -> int:
        """Roll back the table entries whose EVERY position lies in a
        speculative round's rejected span [keep_len, written_len):
        release the dirty block and remap the entry to a fresh one. The
        boundary block holding position keep_len-1 stays — its rejected
        tail is dead under the position mask and the next round's writes
        land on it before it is ever exposed.

        Written blocks are always PRIVATE (writes never target shared
        blocks — try_admit caps the shared span below the first write),
        so each release frees its block; allocating right after can
        therefore never come up dry (release-first guarantees the pool
        holds at least the block just freed). Both halves are enforced:
        a refcounted rollback block or a failed realloc is an invariant
        breach, not a condition to handle."""
        enforce(not self.window,
                "rollback remaps the block table alone: a model with window "
                "layers keeps a second table whose released blocks it could "
                "not bring back", exc=InvalidArgumentError)
        bs = self.block_size
        first = -(-int(keep_len) // bs)          # first fully-rejected block
        last = (int(written_len) - 1) // bs      # last written block
        n = 0
        for j in range(first, min(last + 1, len(table.blocks))):
            freed = self.pool.release(table.blocks[j])
            enforce(freed,
                    f"speculative rollback hit shared block "
                    f"{table.blocks[j]} (logical {j}) — writes must "
                    f"never land in shared blocks",
                    exc=InvalidArgumentError)
            nb = self.pool.alloc()
            enforce(nb is not None, "alloc after release came up dry",
                    exc=InvalidArgumentError)
            table.blocks[j] = nb
            n += 1
        self.rolled_back_blocks += n
        return n

    # -- two-tier (host) lifecycle -----------------------------------------
    def evict_table_to_host(self, table: BlockTable,
                            written_len: int) -> Optional[SpillRecord]:
        """Suspend a resident table: release every PRIVATE device block
        back to the pool and charge the CONTENT-bearing ones (logical
        blocks covering positions [shared_len, written_len)) to the
        host tier. Shared prefix blocks keep their refs — they are
        pinned on device (highest-fanout bytes; HostTierConfig.
        pin_index_nodes). Returns None — spill refused — when the host
        tier cannot hold the content; otherwise the SpillRecord the
        engine needs to know which logical entries to snapshot.

        Private blocks must free on release (writes never land in
        shared blocks — the same invariant `rollback` enforces); a
        refcounted private block here is a breach, not a condition."""
        enforce(self.host_tier is not None,
                "evict_table_to_host without a host tier",
                exc=InvalidArgumentError)
        enforce(not self.window,
                "the host tier spills the block table alone, not the window "
                "table", exc=InvalidArgumentError)
        bs = self.block_size
        n_content = -(-int(written_len) // bs)   # blocks with live rows
        spilled = [j for j in range(table.n_shared,
                                    min(n_content, len(table.blocks)))]
        if self.host_blocks_used + len(spilled) \
                > self.host_tier.host_blocks:
            return None
        for j in range(table.n_shared, len(table.blocks)):
            # full prompt blocks may ALSO be held by the prefix index
            # (note_block_filled registered them) — releasing our ref
            # then leaves them device-resident as cache, possibly
            # evicted later. The engine snapshots the content to host
            # either way, so resume never depends on the index's whim.
            self.pool.release(table.blocks[j])
            table.blocks[j] = 0          # dead mapping until reload
        self.host_blocks_used += len(spilled)
        self.host_evictions += len(spilled)
        return SpillRecord(spilled, len(table.blocks))

    def reload_table_from_host(self, table: BlockTable,
                               rec: SpillRecord
                               ) -> Optional[List[Tuple[int, int]]]:
        """Resume a suspended table: re-allocate a device block for
        every private logical entry (evicting cached prefixes LRU under
        pressure, exactly like admission) and release the host-tier
        charge. Returns [(logical_j, new_physical)] for the
        CONTENT-bearing entries — the h2d copy list, in the
        SpillRecord's ascending order — or None (everything rolled
        back, host charge untouched) when the device pool cannot cover
        the resume yet."""
        enforce(len(table.blocks) == rec.n_blocks,
                f"spill record spans {rec.n_blocks} blocks but the "
                f"table has {len(table.blocks)}",
                exc=InvalidArgumentError)
        got: List[int] = []
        for j in range(table.n_shared, len(table.blocks)):
            b = self._alloc_or_evict()
            if b is None:                # roll back, stay suspended
                for held in got:
                    self.pool.release(held)
                return None
            got.append(b)
        for j, b in zip(range(table.n_shared, len(table.blocks)), got):
            table.blocks[j] = b
        self.host_blocks_used -= len(rec.spilled)
        self.host_reloads += len(rec.spilled)
        self.blocks_allocated_total += len(got)
        return [(j, table.blocks[j]) for j in rec.spilled]

    def refund_host_charge(self, n: int):
        """Return `n` host-tier blocks whose spill will never reload —
        a request released while host-resident (drain/shutdown). A
        pager METHOD (not a raw ledger write) so the shadow-state
        sanitizer can mirror the refund and hold the two-tier identity
        through it."""
        enforce(0 <= n <= self.host_blocks_used,
                f"host refund of {n} blocks underflows the ledger "
                f"({self.host_blocks_used} used)",
                exc=InvalidArgumentError)
        self.host_blocks_used -= n

    def check_two_tier(self):
        """The r23 accounting identity over BOTH tiers (the ISSUE's
        `used_dev + used_host + free == total`), on top of the device
        pool's own refcount/free-list exactness (`BlockPool.check`)."""
        self.pool.check()
        cap = self.host_tier.host_blocks if self.host_tier else 0
        enforce(0 <= self.host_blocks_used <= cap,
                f"host tier accounting broken: {self.host_blocks_used} "
                f"used of {cap}", exc=InvalidArgumentError)
        used_dev, free_dev = self.pool.n_used, self.pool.n_free
        used_host = self.host_blocks_used
        free_host = cap - used_host
        total = (self.pool.n_blocks - 1) + cap
        enforce(used_dev + used_host + free_dev + free_host == total,
                f"two-tier identity broken: {used_dev}+{used_host}+"
                f"{free_dev}+{free_host} != {total}",
                exc=InvalidArgumentError)

    # -- introspection ----------------------------------------------------
    def stats(self) -> Dict:
        return {
            "n_blocks": self.pool.n_blocks,
            "block_size": self.block_size,
            "blocks_used": self.pool.n_used,
            "blocks_free": self.pool.n_free,
            "blocks_cached": self.index.n_cached,
            "prefix_sharing": self.prefix_sharing,
            "admitted": self.n_admitted,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": (self.prefix_hits / self.n_admitted
                                if self.n_admitted else 0.0),
            "shared_blocks_total": self.shared_blocks_total,
            "blocks_allocated_total": self.blocks_allocated_total,
            "blocks_per_request": (self.blocks_allocated_total
                                   / self.n_admitted
                                   if self.n_admitted else 0.0),
            "evictions": self.evictions,
            "cow_copies": self.cow_copies,
            "rolled_back_blocks": self.rolled_back_blocks,
            "block_state": None if not self.block_state else {
                "restores": self.state_restores,
                "snapshots": self.state_snapshots,
                "blocks_with_snapshot": int(self._snap.sum())},
            "snapshot_pool": None if not self.n_snapshots else {
                "entries": self.n_snapshots,
                "valid": self.snapshots_valid,
                "pinned": sum(p > 0 for p in self._snap_pins),
                "written": self.state_snapshots,
                "restores": self.state_restores,
                "evictions": self.snapshot_evictions,
                "hits_truncated": self.hits_truncated},
            "window": None if not self.window else {
                "positions": self.window,
                "n_blocks": self.wpool.n_blocks,
                "blocks_used": self.wpool.n_used,
                "blocks_free": self.wpool.n_free,
                "tail_nodes": self.tail_nodes,
                "tails_cached": len(self._tails),
                "blocks_released": self.window_blocks_released,
                "tail_lookups": self.window_tail_lookups,
                "tail_hits": self.window_tail_hits,
                "tail_evictions": self.window_tail_evictions,
                "hits_truncated": self.hits_truncated,
                "blocks_held": self.window_blocks_held.tolist()},
            "host_tier": None if self.host_tier is None else {
                "host_blocks": self.host_tier.host_blocks,
                "host_blocks_used": self.host_blocks_used,
                "prefetch_distance": self.host_tier.prefetch_distance,
                "rotate_quantum": self.host_tier.rotate_quantum,
                "host_evictions": self.host_evictions,
                "host_reloads": self.host_reloads,
                "prefetch_hits": self.host_prefetch_hits,
                "prefetch_misses": self.host_prefetch_misses,
                "prefetch_hit_rate": (
                    self.host_prefetch_hits
                    / max(self.host_prefetch_hits
                          + self.host_prefetch_misses, 1)),
            },
        }


def prefill_chunk_tokens(block_size: int, blocks_per_req: int) -> int:
    """The tokens one prefill lane of the mixed tick feeds: whole blocks,
    128 tokens (a lane fills the MXU's rows) where the block table spans
    four such chunks, a quarter of the span (at least a block) below."""
    return block_size * max(1, min(128 // block_size, blocks_per_req // 4))


class PagedKVEngine(ContinuousBatchingEngine):
    """Continuous batching over the paged KV cache: the slot engine's
    scheduler and tick loop, with the per-slot [max_len] KV rows
    replaced by block tables over one shared pool.

    What changes vs the parent (every override is one of the parent's
    named hooks — the scheduler itself is untouched, which is what
    makes the decode-identity guarantee auditable):

    - the compiled tick is `transformer_lm_paged_decode_tick` (in-place
      `paged_cache_write` + `paged_decode_attention` reading the pool
      through the block table; `stats()["paged_attention_lowering"]` says
      which lowering the read took);
    - admission acquires a BlockTable from the `KVPager` (head-of-line
      wait under pool pressure — `_admit_request` returning False);
      prefix hits start the request at `fed = shared_len`, skipping the
      shared span's prefill ticks entirely;
    - completion releases the table; full prompt blocks were offered to
      the prefix index the moment they filled (`_note_position_written`);
    - the KV watermarks split honestly: reserved = pool bytes (pinned),
      used = allocated blocks × block bytes (live paging state);
    - `max_len` means the block-table SPAN (blocks_per_req×block_size —
      the per-request logical ceiling), not a per-slot reservation:
      n_blocks is free to be far smaller than n_slots×blocks_per_req,
      which is the whole capacity play.

    `topk_k` > 0 additionally fetches each tick's top-k log-probs —
    `paged_beam_search`'s scoring surface (greedy serving leaves it 0).

    `model=` (a `models.decoder_spec.DecoderSpec`) describes the block the
    ticks are built from, in place of the six dims (which are the classic
    spec: `DecoderSpec.classic`). A latent-attention spec gets the latent
    paged cache (one pool a layer, a padded row a position:
    `_LatentPagedCache`), a spec with `moe` the routed expert layer over
    the experts it holds; both ticks then bring back, behind the ids and in
    the same copy, the rows every held expert got (`engine/tick`'s
    `experts_touched` and `routed_rows`, `stats()["expert_rows"]`). A spec
    with conv layers gets a second kind of state beside the K/V pools (of
    the attention layers' key/value heads only): a slot's conv state and a
    snapshot of it beside every pool block, which a prefix hit resumes
    from (`models.transformer._ConvState`; `stats()["conv_state"]`,
    `engine/admit`'s `state_restored`, `engine/tick`'s `state_snapshots`).
    A spec with sliding-window layers (`DecoderSpec.window_gqa_moe`) gets a
    SECOND pool of `n_window_blocks` blocks for those layers and a second
    table a request, whose blocks go back to the pool as they slide out of
    the window (`stats()["window_pool"]`; `engine/tick`'s `kv_blocks` then
    counts both pools' reads, `window_blocks` and `window_rows` the window
    part).
    With any such model `speculative=`, `host_tier=`, `kv_quant=`, `quant=`
    and `topk_k` are refused by name: none of them is built for it.
    """

    def __init__(self, n_slots: int = 4, vocab: int = 32000,
                 max_len: int = 64, d_model: int = 512,
                 d_inner: int = 2048, num_heads: int = 8,
                 num_layers: int = 6, dropout: float = 0.0,
                 packed: bool = False, eos_id: Optional[int] = None,
                 scope=None, policy: str = "continuous",
                 cache_prefix: Optional[str] = None, block_size: int = 8,
                 n_blocks: Optional[int] = None,
                 prefix_sharing: bool = True, topk_k: int = 0,
                 quant: Optional[str] = None, kv_quant: bool = False,
                 speculative=None,
                 host_tier: Optional[HostTierConfig] = None,
                 model=None, n_snapshots: int = 0,
                 n_window_blocks: int = 0):
        from ..models.decoder_spec import DecoderSpec
        if model is None:
            model = DecoderSpec.classic(vocab, d_model, d_inner, num_heads,
                                        num_layers, dropout, packed)
        else:
            enforce(isinstance(model, DecoderSpec),
                    f"model must be a DecoderSpec, got "
                    f"{type(model).__name__}", exc=InvalidArgumentError)
            vocab, d_model, d_inner, num_heads, num_layers, dropout, packed \
                = (model.vocab, model.d_model, model.d_inner,
                   model.num_heads, model.num_layers, model.dropout,
                   model.packed)
        #: what the ticks' blocks are made of (`DecoderSpec`)
        self.model = model
        if not model.is_classic:
            spec_on = speculative is not None and speculative is not False
            for option, on in (("speculative", spec_on),
                               ("host_tier", host_tier is not None),
                               ("kv_quant", bool(kv_quant)),
                               ("quant", quant is not None),
                               ("topk_k", bool(topk_k))):
                enforce(not on,
                        f"{option}= is not built for a model with "
                        f"attention {model.attention!r}"
                        + (f" over {model.kv_heads} key/value heads"
                           if model.kv_heads != model.num_heads else "")
                        + (" beside short-convolution layers"
                           if model.conv else "")
                        + (" beside state-space layers" if model.ssm else "")
                        + (" beside gated delta-rule (kda) layers"
                           if model.kda else "")
                        + (" read sparsely through an indexer's pool (a "
                           "draft would need the target's index)"
                           if model.indexer else "")
                        + (" beside sliding-window layers"
                           if model.window else "")
                        + (" and routed experts" if model.moe else "")
                        + ": it walks the classic K/V pools and float32 "
                        "weights"
                        + (", not the conv state (a slot's rows and the "
                           "blocks' snapshots), which it would neither "
                           "roll back, spill, fork nor quantize"
                           if model.conv else "")
                        + (", not the state-space state (a slot's h and conv "
                           "rows, the snapshot pool), which it would "
                           "neither roll back, spill, fork nor quantize"
                           if model.ssm else "")
                        + (", not the delta-rule state (a slot's matrix state "
                           "S and conv rows, the snapshot pool), which it "
                           "would neither roll back, spill, fork nor quantize"
                           if model.kda else "")
                        + (", not the window table (a request's second block "
                           "table over the window pool, whose blocks are "
                           "released behind the window), which it would "
                           "neither roll back, spill, fork nor quantize"
                           if model.window else "")
                        + "; serve this model without it",
                        exc=InvalidArgumentError)
        self._described = not model.is_classic    # built from a spec's kinds
        #: (layer, held expert) -> rows routed to it since construction
        self.expert_rows = np.zeros(
            (len(model.moe_layers), len(model.moe.held) if model.moe else 0),
            np.int64)
        enforce(host_tier is None or speculative is None,
                "host_tier does not compose with speculative decoding "
                "yet: a speculative round's rollback remaps blocks the "
                "suspend/resume swap may hold in flight on the stream — "
                "pager-level rollback composition IS covered "
                "(tests/test_offload.py); pick one per engine",
                exc=InvalidArgumentError)
        if host_tier is not None:
            enforce(isinstance(host_tier, HostTierConfig),
                    f"host_tier must be a HostTierConfig, got "
                    f"{type(host_tier).__name__}",
                    exc=InvalidArgumentError)
        self.host_tier = host_tier
        self.block_size = int(block_size)
        self.blocks_per_req = -(-int(max_len) // self.block_size)
        self.prefix_sharing = bool(prefix_sharing)
        self.topk_k = int(topk_k)
        self.kv_quant = bool(kv_quant)
        # int8 KV block pools (ROADMAP item 2's remaining leg): the pool
        # payload is int8 with one f32 scale per (block, head, row), so a
        # block costs bytes_int8 = nh*bs*(dh+4) instead of nh*bs*dh*4 per
        # k/v per layer. At the SAME byte budget the freed bytes buy
        # extra admitted blocks: the capacity-neutral default n_blocks is
        # scaled up by bytes_f32/bytes_int8 (an explicit n_blocks is
        # honored as-is — the caller owns the budget then).
        dh = d_model // num_heads
        #: bytes one block holds over all layers, by the model's cache kind
        #: (K and V of the key/value heads, attention layers only) ...
        self.block_bytes = model.cache_row_bytes() * self.block_size
        #: ... and of ONE copy of the conv layers' state: a slot holds
        #: one, and every pool block a snapshot (0 without conv layers)
        self.state_bytes = model.state_bytes()
        #: ... and in the window pool (0 without sliding-window layers)
        self.window_block_bytes = model.window_row_bytes() * self.block_size
        self.n_window_blocks = int(n_window_blocks) if model.window else 0
        #: entries of the snapshot pool (state-space and kda layers only:
        #: their state is too large for a snapshot a block)
        self.n_snapshots = int(n_snapshots) \
            if model.recurrent is not None else 0
        enforce(model.recurrent is None or self.n_snapshots >= 1,
                "a model with state-space or kda layers needs n_snapshots "
                ">= 1: without a snapshot every request prefills its whole "
                "prompt", exc=InvalidArgumentError)
        per_blk_f32 = 2 * num_layers * num_heads * self.block_size * dh * 4
        per_blk_i8 = 2 * num_layers * num_heads * self.block_size * (dh + 4)
        self.kv_quant_freed_bytes = 0
        if n_blocks is None:
            # capacity-neutral default: every slot can hold a full-span
            # request (+ null block) — callers size DOWN from here to
            # realize the paging win at fixed bytes
            n_blocks = n_slots * self.blocks_per_req + 1
            if self.kv_quant:
                budget = (n_blocks - 1) * per_blk_f32
                n_blocks = 1 + budget // per_blk_i8
        if self.kv_quant:
            self.kv_quant_freed_bytes = \
                (int(n_blocks) - 1) * (per_blk_f32 - per_blk_i8)
        self.n_blocks = int(n_blocks)
        # how a prompt is consumed, from what the engine is built with: in
        # block-aligned chunks through the prefill lanes of a second,
        # mixed tick program, unless a feature that walks one position a
        # tick is on — a speculative round's rollback, the host tier's
        # resume, beam search's own prefill over the top-k tick, and the
        # int8 pools, whose write quantizes row by row
        # (`paged_cache_write_quant` has no whole-block form; no cell
        # runs them). One shape: `n_lanes` lanes of `chunk_tokens` tokens
        self.n_lanes = 2
        self.chunk_tokens = prefill_chunk_tokens(self.block_size,
                                                 self.blocks_per_req)
        spec_on = speculative is not None and speculative is not False
        if not (spec_on or host_tier is not None or self.kv_quant
                or self.topk_k):
            self.prefill = "chunked"
        self._mixed_step = None
        self._lanes: Sequence[Tuple[GenRequest, int]] = ()
        enforce(self.n_blocks >= self.blocks_per_req + 1,
                f"pool of {self.n_blocks} blocks cannot hold one "
                f"full-span request ({self.blocks_per_req} blocks + the "
                f"null block)", exc=InvalidArgumentError)
        self.pager = KVPager(self.n_blocks, self.block_size,
                             prefix_sharing, host_tier=host_tier,
                             block_state=bool(self.state_bytes)
                             and not self.n_snapshots,
                             n_snapshots=self.n_snapshots,
                             window=model.window,
                             n_window_blocks=self.n_window_blocks)
        if model.window:
            # every slot's bound beside nothing else: a tick's fill can
            # always map what it writes once the index gave its tails up
            bound = self.pager.window_bound(self.chunk_tokens)
            enforce(self.n_window_blocks >= n_slots * bound + 1,
                    f"n_window_blocks = {self.n_window_blocks}: a model "
                    f"with sliding-window layers needs n_slots ({n_slots}) x "
                    f"{bound} blocks (a request's bound at a window of "
                    f"{model.window}, chunks of {self.chunk_tokens} and "
                    f"blocks of {self.block_size}) + the null block",
                    exc=InvalidArgumentError)
        # the pager's `state_restores` (and `snapshot_evictions`) at the
        # last `engine/admit` span, which carries what was added since
        self._state_seen = 0
        self._evictions_seen = 0
        # two-tier scheduler state: per-rid host residency records and
        # the FIFO of suspended requests (admission order — no
        # starvation, same discipline as the head-of-line device wait)
        self._ht_state: Dict[int, Dict] = {}
        self._ht_queue: List[GenRequest] = []
        self._ht_stream = _offload.shared_stream() \
            if host_tier is not None else None
        self._ht_pool = _offload.shared_host_pool() \
            if host_tier is not None else None
        self._ht_per_block_bytes = 0     # measured lazily (first spill)
        self.ht_d2h_bytes = 0            # measured: actual buffer bytes
        self.ht_h2d_bytes = 0
        if cache_prefix is None:
            cache_prefix = f"pgd{next(_ENGINE_SEQ)}"
        super().__init__(
            n_slots=n_slots, vocab=vocab,
            max_len=self.blocks_per_req * self.block_size,
            d_model=d_model, d_inner=d_inner, num_heads=num_heads,
            num_layers=num_layers, dropout=dropout, packed=packed,
            eos_id=eos_id, scope=scope, policy=policy,
            cache_prefix=cache_prefix, quant=quant,
            speculative=speculative)
        if self.prefill == "chunked":
            self._build_mixed_step()

    # -- tick programs ----------------------------------------------------
    def _build_mixed_step(self):
        """The second compiled program: the decode tick's rows plus the
        prefill lanes (`transformer_lm_paged_mixed_tick`), bound to the
        decode tick's own feed arrays and the lanes'. It shares every
        weight and pool with the decode tick by name and declares nothing
        else, so there is no startup to run for it (and a weight-quantized
        engine rewrites it onto the payloads the decode tick already
        holds, as the verify tick does)."""
        from ..core import unique_name
        from ..framework.program import Program, program_guard
        from ..models import transformer
        self._mixed_program, startup = Program(), Program()
        with program_guard(self._mixed_program, startup), \
                unique_name.guard():
            outs = transformer.transformer_lm_paged_mixed_tick(
                n_slots=self.n_slots, n_lanes=self.n_lanes,
                chunk=self.chunk_tokens, n_blocks=self.n_blocks,
                block_size=self.block_size,
                blocks_per_req=self.blocks_per_req,
                cache_prefix=self._cache_prefix, model=self.model,
                n_snapshots=self.n_snapshots,
                n_window_blocks=self.n_window_blocks, **self._builder_dims)
            self._mixed_ids = outs[0]
        self._init_missing_vars(startup)        # nothing, by construction
        if self.quant is not None:
            from ..framework.passes import get_pass
            get_pass("quantize_params_pass",
                     bits=8 if self.quant == "int8" else 4)(
                self._mixed_program, self.scope)
        self._mixed_feeds = _feed_arrays(self._mixed_program,
                                        share=self._feeds)
        self._mixed_step = self._exe.prepare(
            self._mixed_program, dict(self._mixed_feeds),
            self._mixed_fetches(), self.scope,
            name="mixed_tick").bind(self._mixed_feeds)
        # ONE host buffer for both ticks: the decode tick's feeds lead the
        # mixed tick's, so the decode step moves onto that leading span
        # (and its views): a fill writes them once, the decode tick
        # transfers the prefix and the mixed tick the whole
        self._step.bind(self._feeds, share=self._mixed_step)
        self._tok = self._feeds["tick_tok"]
        self._pos = self._feeds["tick_pos"]
        self._from_last = self._feeds["tick_from_last"]
        self._lane_feeds = {n: a for n, a in self._mixed_feeds.items()
                            if n not in self._feeds}
        self._bound_steps["mixed"] = self._mixed_step

    def mixed_tick_hlo(self) -> str:
        """`tick_hlo()` of the mixed tick (chunked engines only)."""
        return self._mixed_step.compiled_hlo()

    def _build_tick_program(self):
        from ..fusion.paged_attention import paged_attention_lowering
        from ..ops.tensor_ops import pool_block_shape
        from ..models import transformer
        # which lowering the tick's cache read takes, decided here by the
        # rule the op itself applies when the tick compiles; on a TPU it
        # raises rather than serve float32 pools from the composite
        d = self._builder_dims
        dh = self.model.d_head
        if self.model.attention == "latent":
            from ..fusion.latent_attention import latent_attention_lowering
            lat = self.model.latent
            self.paged_attention_lowering = latent_attention_lowering(
                lat.row_lanes, lat.kv_lora_rank, d["num_heads"], 1)
        else:
            self.paged_attention_lowering = paged_attention_lowering(
                "int8" if self.kv_quant else self.model.dtype,
                pool_block_shape(self.model.kv_heads, self.block_size,
                                 dh)[-1], 1, dh, self.kv_quant)
        outs = transformer.transformer_lm_paged_decode_tick(
            n_slots=self.n_slots, n_blocks=self.n_blocks,
            block_size=self.block_size,
            blocks_per_req=self.blocks_per_req,
            cache_prefix=self._cache_prefix, topk_k=self.topk_k,
            kv_quant=self.kv_quant, model=self.model,
            n_snapshots=self.n_snapshots,
            n_window_blocks=self.n_window_blocks, **d)
        if self.topk_k:
            (self._next_ids, self.cache_names,
             self._topk_logp, self._topk_ids) = outs
        else:
            self._next_ids, self.cache_names = outs

    def _tick_fetches(self):
        if self.topk_k:
            return [self._next_ids, self._topk_logp, self._topk_ids]
        return [self._next_ids]

    def _mixed_fetches(self):
        """`_tick_fetches` of the mixed tick."""
        return [self._mixed_ids]

    def _prefilling(self, req: GenRequest) -> bool:
        """Does `req` still have prompt tokens for a lane to consume? (A
        one-token engine feeds them through the decode rows: never.)"""
        return self._mixed_step is not None and req.fed < len(req.prompt)

    def _fill_tick_feeds(self, active: Dict[int, GenRequest]):
        tok, pos, from_last = self._tok, self._pos, self._from_last
        btab = self._feeds["tick_btab"]
        wblock = self._feeds["tick_wblock"]
        woff = self._feeds["tick_woff"]
        tok[:] = 0
        pos[:] = 0.0
        from_last[:] = 0
        btab[:] = 0                              # idle slots → null block
        wblock[:] = 0
        woff[:] = 0
        bs = self.block_size
        kv_blocks = kv_rows = 0
        window = self.model.window
        if window:
            wbtab = self._feeds["tick_wbtab"]
            wwblock = self._feeds["tick_wwblock"]
            wbtab[:] = 0
            wwblock[:] = 0
            win_blocks = win_rows = 0
        prefilling = []
        for slot, req in active.items():
            if self._prefilling(req):
                prefilling.append(req)   # a lane feeds it: no decode row
                continue
            if req.next_tok is None:     # the last tick left it on the device
                from_last[slot, 0] = 1
            else:
                tok[slot, 0] = req.next_tok
            pos[slot, 0, 0] = float(req.fed)
            blocks = req.table.blocks
            btab[slot, :len(blocks)] = blocks
            lb, off = divmod(req.fed, bs)
            wblock[slot] = blocks[lb]
            woff[slot] = off
            kv_blocks += lb + 1      # the blocks this slot's read spans
            kv_rows += req.fed + 1   # ... and the positions it attends
            if window:
                # the row's block of the window pool, mapped now; the read
                # starts at the block of position fed - (window - 1)
                self.pager.map_window(req.table, req.fed)
                wb, lo = req.table.window_blocks, req.table.window_lo
                wbtab[slot, lo:lb + 1] = wb[lo:lb + 1]
                wwblock[slot] = wb[lb]
                win_blocks += lb + 1 - self.pager.window_first(req.fed)
                win_rows += min(req.fed + 1, window)
        self._tick_attrs["kv_blocks"] = kv_blocks
        self._tick_attrs["decode_rows"] = kv_rows
        if self.model.indexer is not None:
            # the sparse read's rows (the lanes add theirs): what they hold
            # and what the selection lets them attend, by arithmetic on the
            # positions (fusion/sparse_latent_attention.py `select`)
            at = np.asarray([r.fed for r in active.values()
                             if not self._prefilling(r)], np.int64)
            self._tick_attrs.update(self._sparse_counts(at, self.n_slots))
        if self.n_snapshots:
            # the live decode rows, whose state-space state the tick reads
            # and writes (a slot in prefill moves its state in a lane)
            self._tick_attrs["state_rows"] = len(active) - len(prefilling)
        if window:
            # `kv_blocks`: the blocks the reads span in BOTH pools
            self._tick_attrs["kv_blocks"] += win_blocks
            self._tick_attrs["window_blocks"] = win_blocks
            self._tick_attrs["window_rows"] = win_rows
        if self._mixed_step is not None:
            self._fill_lanes(prefilling)

    def _sparse_counts(self, positions: np.ndarray, tick_rows: int,
                       before: int = 0) -> Dict[str, int]:
        """`engine/tick`'s counts of the sparse latent read over rows at
        `positions`: `dsa_rows`, the positions they hold
        (`dsa_live_positions`), the positions the selection attends (the
        best `top_groups` whole groups and the tail:
        `dsa_selected_positions`), the pooled rows the tick writes into
        the index pool (`index_pool_rows`: a row a decode row, a row a group
        a lane touches) and the rows the selection SORTED
        (`dsa_scored_rows`: the op's own `rung`, whole steps of 8, for these
        rows and the `before` live ones counted already, of a tick of
        `tick_rows`), each over the spec's sparse layers."""
        from ..fusion.sparse_latent_attention import rung
        ix, n = self.model.indexer, len(self.model.attention_layers)
        held = positions + 1
        picked = np.minimum(held // ix.kpool, ix.top_groups) * ix.kpool \
            + held % ix.kpool
        return {"dsa_rows": n * len(positions),
                "dsa_live_positions": n * int(held.sum()),
                "dsa_selected_positions": n * int(picked.sum()),
                "index_pool_rows": n * len(positions),
                "dsa_scored_rows": n * rung(before + len(positions),
                                            tick_rows)}

    def _fill_lanes(self, prefilling: List[GenRequest]):
        """Give the tick's lanes to the slots in prefill, in admission
        order (`prefilling` comes in `_active`'s order, which is it), and
        fill the lanes' feeds: each lane its request's next `chunk_tokens`
        prompt tokens (fewer on the last chunk), from a block boundary. A
        slot in prefill beyond the lanes waits this tick out, and the wait
        is counted on it (`lane_wait_ticks`) and on the tick
        (`lane_waiting`). Counts what the lanes take (`prefill`,
        `prefill_tokens`; `kv_blocks` adds the blocks their reads span)."""
        attrs = self._tick_attrs
        lanes = []
        tokens = snapshots = lane_blocks = 0
        for i in range(self.n_lanes, len(prefilling)):
            prefilling[i].lane_wait_ticks += 1
        attrs["lane_waiting"] = max(len(prefilling) - self.n_lanes, 0)
        if prefilling:
            lf = self._lane_feeds
            for a in lf.values():
                a[:] = 0                         # idle lane → null block
            if self.n_snapshots:
                lf["lane_snap_src"][:] = -1      # ... and no snapshot
                lf["lane_snap_dst"][:] = -1
            bs, C = self.block_size, self.chunk_tokens
            cb = C // bs
            window = self.model.window
            for lane, req in enumerate(prefilling[:self.n_lanes]):
                k0, blocks = req.fed, req.table.blocks
                n = min(C, len(req.prompt) - k0)
                b0, nb = k0 // bs, -(-n // bs)
                if window:
                    self.pager.map_window(req.table, k0, n)
                    wb, lo = req.table.window_blocks, req.table.window_lo
                    lf["lane_wbtab"][lane, lo:b0 + nb] = wb[lo:b0 + nb]
                    lf["lane_wwblocks"][lane * cb:lane * cb + nb] = \
                        wb[b0:b0 + nb]
                    span = b0 + nb - self.pager.window_first(k0)
                    attrs["kv_blocks"] += span
                    attrs["window_blocks"] += span
                lf["lane_tok"][lane, :n] = req.prompt[k0:k0 + n]
                lf["lane_pos"][lane, 0, 0] = float(k0)
                lf["lane_btab"][lane, :len(blocks)] = blocks
                lf["lane_wblocks"][lane * cb:lane * cb + nb] = \
                    blocks[b0:b0 + nb]
                lf["lane_rows"][lane] = n
                lf["lane_last"][lane] = lane * C + n - 1
                if self.n_snapshots:
                    lf["lane_slot"][lane] = req.slot
                    snapshots += self._fill_lane_snapshots(lane, req, k0, n)
                elif self.state_bytes:
                    # the lane leaves its last state in the request's slot,
                    # and a snapshot beside every block it fills
                    lf["lane_slot"][lane] = req.slot
                    snapshots += (k0 + n) // bs - b0
                lanes.append((req, n))
                tokens += n
                lane_blocks += b0 + nb
        self._lanes = lanes
        if self.model.indexer is not None and lanes:
            kpool = self.model.indexer.kpool
            n_layers = len(self.model.attention_layers)
            more = self._sparse_counts(
                np.concatenate([np.arange(req.fed, req.fed + n)
                                for req, n in lanes]),
                self.n_slots + self.n_lanes * self.chunk_tokens,
                before=attrs["dsa_rows"] // n_layers)
            # a lane writes a pooled row a group it touches, not a row a row
            more["index_pool_rows"] = n_layers * sum(
                -(-n // kpool) for _, n in lanes)
            # the mixed tick's rows are sorted together, the decode rows among
            # them: ONE count
            attrs["dsa_scored_rows"] = more.pop("dsa_scored_rows")
            for key, value in more.items():
                attrs[key] += value
        attrs["kv_blocks"] += lane_blocks
        attrs["prefill"] = len(lanes)
        attrs["prefill_tokens"] = tokens
        # the lanes' part of `kv_blocks` (the first pool's), apart from the
        # decode rows': a lane reads its request's blocks up to its chunk
        attrs["lane_kv_blocks"] = lane_blocks
        if self.state_bytes:
            attrs["state_snapshots"] = snapshots

    def _fill_lane_snapshots(self, lane: int, req: GenRequest, k0: int,
                             n: int) -> int:
        """The snapshot pool's part of a lane's feeds: the entry the chunk
        starts from (the request's first chunk after a prefix hit; -1: its
        slot's own state), and the entry it writes, where the chunk crosses
        the end of the prompt's last whole block (-1: none). Returns the
        snapshots the lane writes."""
        lf, table = self._lane_feeds, req.table
        if table.snapshot is not None and k0 == table.shared_len:
            lf["lane_snap_src"][lane] = table.snapshot
        end = len(req.prompt) // self.block_size * self.block_size
        if not k0 < end <= k0 + n:
            return 0
        entry = self.pager.take_snapshot_entry(
            table, end // self.block_size - 1)
        if entry is None:
            return 0
        lf["lane_snap_dst"][lane] = entry
        lf["lane_snap_rows"][lane] = end - k0
        return 1

    def _launch_tick(self):
        # a tick with a slot in prefill is the mixed program (the decode
        # rows ride in it); any other is the decode tick, unchanged
        mixed = bool(self._lanes)
        self._tick_attrs["mixed"] = int(mixed)
        return (self._run_bound_step(self._mixed_step, "mixed")
                if mixed else super()._launch_tick())

    def _note_tick_counts(self, tick, ids: np.ndarray):
        # a routed model's ticks end in one count a (layer, held expert):
        # the rows the tick routed to it (`_TickRows.with_counts`)
        total = self.expert_rows
        if total.size:
            counts = ids[-total.size:, 0].reshape(total.shape)
            total += counts
            tick.attrs["experts_touched"] = int(np.count_nonzero(counts))
            # maximal runs of touched experts in their stored order, over
            # the routed layers: a run's first expert has no touched one
            # before it in its layer
            on = counts > 0
            tick.attrs["expert_runs"] = int(
                np.count_nonzero(on[:, 0])
                + np.count_nonzero(on[:, 1:] & ~on[:, :-1]))
            tick.attrs["routed_rows"] = int(counts.sum())
            tick.attrs["expert_rows"] = counts.ravel().tolist()
        elif self._described:
            # a described block without a routed layer streams no expert:
            # the tick says so, as the routed models' ticks say how many
            tick.attrs["experts_touched"] = 0

    def _commits_every_tick(self) -> bool:
        # the host tier's `_pre_tick` moves blocks between ticks, and
        # `paged_beam_search` reads a top-k tick's fetches at once
        return (super()._commits_every_tick() or self.host_tier is not None
                or bool(self.topk_k))

    def _advance_positions(self, active: Dict[int, GenRequest]
                           ) -> List[tuple]:
        lanes = self._lanes
        if not lanes:
            return super()._advance_positions(active)
        # the decode rows first (a slot in prefill sent none: judged
        # before any lane advances), then each lane's chunk; the lanes'
        # rows of ids follow the S decode rows
        emits = [(req, slot) for slot, req in active.items()
                 if not req.closed and not self._prefilling(req)
                 and self._advance_position(req)]
        emits += [(req, self.n_slots + lane)
                  for lane, (req, n) in enumerate(lanes)
                  if self._advance_chunk(req, n)]
        return emits

    def _advance_chunk(self, req: GenRequest, n: int) -> bool:
        """A lane consumed `req`'s next `n` prompt tokens: advance it and
        offer every block the chunk completed to the prefix cache. Returns
        True when that was the prompt's end: the lane's row of ids (the last
        row's argmax) is then the first sampled token, of this tick."""
        bs = self.block_size
        k0 = req.fed
        req.fed = k0 + n
        for lb in range(k0 // bs, req.fed // bs):
            self.pager.note_block_filled(req.table, lb, req.prompt,
                                         snapshot=self.pager.block_state)
        if self.n_snapshots:
            self.pager.snapshot_read(req.table)   # the chunk started there
            if req.table.snapshot_write is not None:
                self.pager.snapshot_written(req.table, req.prompt)
        self.pager.slide_window(req.table, req.fed)
        if req.fed < len(req.prompt):
            req.next_tok = req.prompt[req.fed]
            return False
        return True

    def _note_tick_writes(self, active: Dict[int, GenRequest]):
        # shadow-state sanitizer: every position this tick writes must
        # target a live, EXCLUSIVELY-held block (the CoW contract) —
        # checked against the ownership model before dispatch
        san = self.pager.sanitizer
        if san is not None:
            for req in active.values():
                if not self._prefilling(req):
                    san.note_write(req.table, req.fed)
            for req, n in self._lanes:
                for p in range(req.fed, req.fed + n):
                    san.note_write(req.table, p)

    # -- scheduler hooks --------------------------------------------------
    def _admit_request(self, req: GenRequest) -> bool:
        need_len = min(len(req.prompt) + req.max_new, self.max_len)
        table = self.pager.try_admit(req.prompt, need_len)
        if table is not None:
            req.table = table
            req.shared_len = table.shared_len
            if table.shared_len:
                # the shared span's K/V is already resident and
                # byte-exact (deterministic compute) — skip its
                # prefill ticks. A model with conv layers resumes from
                # the state snapshot of the span's last block: its first
                # chunk starts there (`fusion/short_conv.py`), and the
                # pager served the span only up to a block that has one
                req.fed = table.shared_len
                req.next_tok = req.prompt[table.shared_len]
            if self.host_tier is not None:
                self._ht_state[req.rid] = {"state": "resident",
                                           "resume_tick": self.n_ticks}
            return True
        if self.host_tier is None:
            return False                         # head-of-line wait
        # two-tier admission: the device pool is dry but tick slots are
        # not — admit SUSPENDED. The request holds its slot with ZERO
        # bytes on either tier (it has never ticked); it starts decoding
        # when a resident finishes or the rotation quantum frees blocks.
        # This is exactly where admitted concurrency beats the
        # device-only ceiling.
        req.table = None
        self._ht_state[req.rid] = {"state": "waiting",
                                   "spill": None, "bufs": None,
                                   "d2h": None, "h2d": None,
                                   "suspend_tick": self.n_ticks}
        self._ht_queue.append(req)
        return True

    def _admit_pool_attrs(self) -> Dict[str, int]:
        attrs = {"pool_used": self.pager.pool.n_used,
                 "pool_blocks": self.n_blocks}
        if self.model.window:
            attrs["window_pool_used"] = self.pager.wpool.n_used
            attrs["window_tail_hits"] = self.pager.window_tail_hits
            attrs["hits_truncated"] = self.pager.hits_truncated
        if self.state_bytes:
            now = self.pager.state_restores
            attrs["state_restored"] = now - self._state_seen
            self._state_seen = now
        if self.n_snapshots:
            attrs["snapshots_used"] = self.pager.snapshots_valid
            now = self.pager.snapshot_evictions
            attrs["snapshot_evictions"] = now - self._evictions_seen
            self._evictions_seen = now
        return attrs

    def _release_request(self, req: GenRequest):
        if req.table is not None:
            self.pager.release(req.table)
            req.table = None
        st = self._ht_state.pop(req.rid, None)
        if st is not None and st.get("bufs"):
            # a request released while host-resident (drain/shutdown):
            # its spill never reloads — return the host bytes
            if st.get("d2h") is not None:
                st["d2h"].wait(timeout=60.0)
            for buf in st["bufs"].values():
                self._ht_pool.free(buf)
            self.pager.refund_host_charge(len(st["spill"].spilled))
        if st is not None and req in self._ht_queue:
            self._ht_queue.remove(req)

    def _note_position_written(self, req: GenRequest, pos: int):
        if (pos + 1) % self.block_size == 0:
            self.pager.note_block_filled(req.table,
                                         pos // self.block_size,
                                         req.prompt)
        self.pager.slide_window(req.table, pos + 1)

    # -- two-tier scheduler (host_tier=) ----------------------------------
    @staticmethod
    def _remaining_ticks(req: GenRequest) -> int:
        """Upper bound on ticks until `req` finishes (eos can only
        shorten it — a prefetch issued against this bound can be late,
        never early; late shows up honestly as a prefetch miss)."""
        prefill = max(0, len(req.prompt) - 1 - req.fed)
        return prefill + max(0, req.max_new - len(req.tokens))

    def _pre_tick(self, active: Dict[int, GenRequest]
                  ) -> Dict[int, GenRequest]:
        """The swap scheduler, run between ticks on the compute thread
        (the single sanctioned writer of the donated cache arrays —
        `PreparedStep.refresh_state` re-points the bound step after
        commits). In order: resume waiters FIFO while the device pool
        covers them; rotate (evict the resident with the most remaining
        work) when the head waiter has starved a full quantum; issue
        h2d prefetches `prefetch_distance` ticks ahead of the projected
        resume. Returns the RESIDENT subset — suspended requests hold
        their slots but do not tick."""
        if self.host_tier is None:
            return active
        with _tracing.span("dispatch", "engine/pre_tick"):
            return self._swap_schedule(active)

    def _swap_schedule(self, active: Dict[int, GenRequest]
                       ) -> Dict[int, GenRequest]:
        tick = self.n_ticks
        while self._ht_queue and self._try_resume(self._ht_queue[0]):
            self._ht_queue.pop(0)
        quantum = self.host_tier.rotate_quantum
        if self._ht_queue and quantum:
            head = self._ht_queue[0]
            if tick - self._ht_state[head.rid]["suspend_tick"] >= quantum:
                victim = self._pick_victim(active, tick)
                if victim is not None:
                    self._suspend_resident(victim, tick)
                    if self._try_resume(head):
                        self._ht_queue.pop(0)
        self._maybe_prefetch(active, tick)
        resident = {s: r for s, r in active.items()
                    if self._ht_state[r.rid]["state"] == "resident"}
        if not resident and active:
            # nothing resident can only mean the pool is all free or
            # index-cached — the head waiter MUST resume (else the
            # two-tier scheduler would deadlock; make that loud)
            head = self._ht_queue[0]
            enforce(self._try_resume(head),
                    "two-tier scheduler wedged: no resident requests "
                    "and the head waiter cannot acquire device blocks",
                    exc=InvalidArgumentError)
            self._ht_queue.pop(0)
            resident = {s: r for s, r in active.items()
                        if self._ht_state[r.rid]["state"] == "resident"}
        return resident

    def _try_resume(self, req: GenRequest) -> bool:
        """Make a suspended request resident: never-ticked waiters go
        through normal admission (prefix sharing included); spilled
        waiters re-acquire device blocks and commit their staged h2d
        content. False = capacity still short, stay queued."""
        st = self._ht_state[req.rid]
        if st["state"] == "waiting":
            need_len = min(len(req.prompt) + req.max_new, self.max_len)
            table = self.pager.try_admit(req.prompt, need_len)
            if table is None:
                return False
            req.table = table
            req.shared_len = table.shared_len
            if table.shared_len:
                req.fed = table.shared_len
                req.next_tok = req.prompt[table.shared_len]
        else:                                    # spilled, has content
            moves = self.pager.reload_table_from_host(req.table,
                                                      st["spill"])
            if moves is None:
                return False
            if moves:
                if st.get("d2h") is not None:
                    # surfaces a failed spill copy here instead of
                    # letting a zeroed host buffer reach the cache
                    st["d2h"].wait(timeout=60.0)
                ticket = st.get("h2d")
                hit = ticket is not None and ticket.done()
                if ticket is None:
                    ticket = self._stage_h2d(st)
                self.pager.host_prefetch_hits += 1 if hit else 0
                self.pager.host_prefetch_misses += 0 if hit else 1
                _offload.note_prefetch(hit)
                staged = ticket.wait(timeout=60.0)
                san = self.pager.sanitizer
                if san is not None:
                    # prefetch-after-use gate: the wait() above must
                    # have landed the ticket before the scatter commits
                    san.note_h2d_commit(ticket)
                self._commit_h2d(moves, staged)
                self.ht_h2d_bytes += ticket.nbytes
            for buf in (st["bufs"] or {}).values():
                self._ht_pool.free(buf)
            st.update(spill=None, bufs=None, d2h=None, h2d=None)
        st.update(state="resident", resume_tick=self.n_ticks)
        return True

    def _suspend_resident(self, req: GenRequest, tick: int):
        """Evict a resident request's private blocks to the host tier:
        the pager trades the device blocks for host capacity, the
        engine gathers the spilled rows out of the cache arrays HERE,
        on the compute thread, before the next tick can run — the r21
        donated decode tick hands the cache buffers back to XLA every
        dispatch, so a lazily-captured array may be backing a reused
        buffer by the time a stream thread reads it (observed: silent
        zeros, not an error). Only the host-side copy into the pinned
        pool buffer rides the transfer stream; that copy is what the
        d2h byte accounting and the `offload` span measure."""
        st = self._ht_state[req.rid]
        table = req.table
        phys = {j: table.blocks[j] for j in range(len(table.blocks))}
        rec = self.pager.evict_table_to_host(table, req.fed)
        if rec is None:
            return                               # host tier full: keep
        st.update(state="spilled", spill=rec, suspend_tick=tick,
                  d2h=None, h2d=None, bufs=None)
        self._ht_queue.append(req)
        if not rec.spilled:
            return                               # no content to move
        src = np.asarray([phys[j] for j in rec.spilled])
        # eager gather (compute thread): forces the read BEFORE the
        # next donated dispatch can recycle the cache buffers
        snaps = {name: np.asarray(self.scope.get(name)[src])
                 for name in self.cache_names}
        bufs, total = {}, 0
        for name, snap in snaps.items():
            buf = self._ht_pool.alloc(snap.shape, snap.dtype, "kv")
            bufs[name] = buf
            total += buf.nbytes

        def _spill(snaps=snaps, bufs=bufs):
            for name, snap in snaps.items():
                np.copyto(bufs[name].array, snap)

        st["bufs"] = bufs
        st["d2h"] = self._ht_stream.submit("d2h", _spill, total,
                                           tag=req.request_id)
        self.ht_d2h_bytes += total
        if not self._ht_per_block_bytes:
            self._ht_per_block_bytes = total // len(src)
        _offload.note_eviction(len(src))

    def _pick_victim(self, active: Dict[int, GenRequest],
                     tick: int) -> Optional[GenRequest]:
        """Rotation victim: the resident request with the MOST
        remaining work (it blocks the queue longest), provided it has
        been resident a full quantum (anti-thrash) and is not about to
        finish anyway. None = nobody qualifies, head keeps waiting."""
        quantum = self.host_tier.rotate_quantum
        best, best_rem = None, 0
        for req in active.values():
            st = self._ht_state[req.rid]
            if st["state"] != "resident":
                continue
            if tick - st.get("resume_tick", 0) < quantum:
                continue
            rem = self._remaining_ticks(req)
            if rem > max(best_rem, 2):
                best, best_rem = req, rem
        return best

    def _maybe_prefetch(self, active: Dict[int, GenRequest], tick: int):
        """Issue the head waiter's h2d staging `prefetch_distance`
        ticks ahead of its projected resume — the earlier of (a) the
        soonest resident finish and (b) the next rotation boundary.
        `offload.prefetch_issue_tick` is the ONE policy helper here and
        in `lint_program --offload` (linted == shipped)."""
        if not self._ht_queue:
            return
        head = self._ht_queue[0]
        st = self._ht_state[head.rid]
        if st["state"] != "spilled" or st["h2d"] is not None \
                or not st["spill"].spilled:
            return
        etas = [self._remaining_ticks(r) for r in active.values()
                if self._ht_state[r.rid]["state"] == "resident"]
        eta = min(etas) if etas else 0
        quantum = self.host_tier.rotate_quantum
        if quantum:
            eta = min(eta, max(quantum - (tick - st["suspend_tick"]), 0))
        if _offload.prefetch_issue_tick(
                tick + eta, self.host_tier.prefetch_distance) <= tick:
            self._stage_h2d(st)

    def _stage_h2d(self, st: Dict):
        """Stage the spilled content as device-placed arrays on the
        stream (on TPU this is the PCIe h2d; the block scatter at
        commit is an on-device copy). FIFO ordering makes the
        wait-for-d2h free: the spill job is ahead in the same queue."""
        bufs = st["bufs"]
        total = sum(b.nbytes for b in bufs.values())

        def _stage(bufs=bufs):
            import jax.numpy as jnp
            return {name: jnp.asarray(b.array)
                    for name, b in bufs.items()}

        st["h2d"] = self._ht_stream.submit("h2d", _stage, total,
                                           tag="prefetch")
        return st["h2d"]

    def _commit_h2d(self, moves: List[Tuple[int, int]], staged: Dict):
        """Scatter the staged block rows into the live cache arrays at
        their NEW physical ids, on the compute thread between ticks
        (single-writer), then mark the bound step's state stale so
        `_plain_tick` re-points it before dispatch."""
        dst = np.asarray([b for _, b in moves])
        for name, rows in staged.items():
            arr = self.scope.get(name)
            if hasattr(arr, "at"):
                arr = arr.at[dst].set(rows)
            else:
                arr = np.asarray(arr)
                arr[dst] = rows
            self.scope.set_var(name, arr)
        self._target_state_owner = "offload"

    # -- speculative-decoding hooks (serving/speculative.py) --------------
    def _build_verify_tick(self, gamma):
        from ..models import transformer
        return transformer.transformer_lm_paged_spec_verify_tick(
            self.n_slots, gamma, n_blocks=self.n_blocks,
            block_size=self.block_size,
            blocks_per_req=self.blocks_per_req,
            cache_prefix=self._cache_prefix, kv_quant=self.kv_quant,
            **self._builder_dims)

    def _fill_verify_row(self, feeds, slot, req, g):
        super()._fill_verify_row(feeds, slot, req, g)
        blocks = req.table.blocks
        feeds["spec_btab"][slot, :len(blocks)] = blocks
        bs = self.block_size
        san = self.pager.sanitizer
        for j in range(g):
            lb, off = divmod(req.fed + j, bs)
            feeds["spec_wblock"][slot, j] = blocks[lb]
            feeds["spec_woff"][slot, j] = off
            if san is not None:
                # every speculative verify lane writes in place — each
                # target must be exclusively held (CoW contract)
                san.note_write(req.table, req.fed + j)

    def _spec_capable(self, req, g) -> bool:
        # the round's G writes must stay inside the request's block-table
        # span (host-side block lookup would index past the table)
        return (req.fed + g <= self.max_len
                and req.fed + g <= len(req.table.blocks) * self.block_size)

    def _spec_rollback(self, req, keep_len, written_len) -> int:
        return self.pager.rollback(req.table, keep_len, written_len)

    # -- limits / accounting ----------------------------------------------
    def _enforce_request_fits(self, prompt, max_new):
        enforce(len(prompt) + int(max_new) <= self.max_len,
                f"prompt({len(prompt)}) + max_new({max_new}) exceeds the "
                f"paged engine's per-request block-table span "
                f"blocks_per_req({self.blocks_per_req}) x block_size"
                f"({self.block_size}) = {self.max_len} tokens; pool "
                f"capacity ({self.n_blocks - 1} blocks) governs "
                f"ADMISSION (requests queue for blocks), not submission",
                exc=InvalidArgumentError)

    def _stamp_kv_watermarks(self, active: Dict[int, GenRequest]):
        # reserved = the whole pool (pinned at construction); used =
        # blocks actually allocated right now — live paging state, the
        # split the slot engine can only fake (its rows are always
        # reserved whole)
        _obs_memory.update_watermark("kv_cache_bytes",
                                     self._kv_bytes_static)
        if self.model.window:
            # two pools under the one pair: reserved is both, used each
            # pool's allocated blocks at its own block's bytes
            used = (self.pager.pool.n_used * self.block_bytes
                    + self.pager.wpool.n_used * self.window_block_bytes)
        else:
            used = self.pager.pool.n_used * (
                self._kv_bytes_static / max(self.n_blocks, 1))
        _obs_memory.update_watermark("kv_cache_used_bytes", used)

    def _init_metrics(self):
        super()._init_metrics()
        r = self.metrics_registry
        pager = self.pager
        r.gauge("ptpu_engine_block_pool_blocks_used",
                "Allocated blocks in the paged KV pool.",
                fn=lambda: pager.pool.n_used)
        r.gauge("ptpu_engine_block_pool_blocks_free",
                "Free blocks in the paged KV pool.",
                fn=lambda: pager.pool.n_free)
        r.gauge("ptpu_engine_block_pool_occupancy",
                "Fraction of the paged KV pool's blocks allocated.",
                fn=lambda: (pager.pool.n_used
                            / max(pager.pool.n_blocks - 1, 1)))
        r.gauge("ptpu_engine_prefix_hit_rate",
                "Fraction of admitted requests that shared a cached "
                "prompt prefix.",
                fn=lambda: pager.stats()["prefix_hit_rate"])
        r.gauge("ptpu_engine_blocks_per_request",
                "Mean PRIVATE blocks allocated per admitted request "
                "(shared prefix blocks excluded — they are the saving).",
                fn=lambda: pager.stats()["blocks_per_request"])
        r.gauge("ptpu_engine_block_evictions_total",
                "Cached prefix blocks evicted (LRU, leaf-first) under "
                "pool pressure.", fn=lambda: pager.evictions)
        r.gauge("ptpu_engine_cow_copies_total",
                "Copy-on-write block copies at fork divergence points.",
                fn=lambda: pager.cow_copies)
        r.gauge("ptpu_engine_spec_rolled_back_blocks_total",
                "Block-table entries rolled back to fresh blocks after "
                "speculative verify rejected their whole span.",
                fn=lambda: pager.rolled_back_blocks)
        r.gauge("ptpu_engine_paged_attention_kernel",
                "1 when the compiled tick reads the KV pool through the "
                "block table with the Pallas kernel, 0 when it takes the "
                "composite that gathers the dense table view.",
                fn=lambda: int(self.paged_attention_lowering == "kernel"))
        r.gauge("ptpu_engine_kv_quant_freed_bytes",
                "Bytes the int8 KV block pools save vs f32 pools at the "
                "same block count (0 with kv_quant off).",
                fn=lambda: self.kv_quant_freed_bytes)
        if self.host_tier is not None:
            _offload.offload_metrics()   # ptpu_offload_* (default reg)
            r.gauge("ptpu_engine_host_blocks_used",
                    "KV blocks resident on the host tier (spilled).",
                    fn=lambda: pager.host_blocks_used)
            r.gauge("ptpu_engine_suspended_requests",
                    "Admitted requests currently holding a tick slot "
                    "without device blocks (two-tier suspend).",
                    fn=lambda: len(self._ht_queue))
            r.gauge("ptpu_engine_host_prefetch_hit_rate",
                    "Fraction of host-tier resumes whose h2d prefetch "
                    "had already landed.",
                    fn=lambda: pager.stats()["host_tier"]
                    ["prefetch_hit_rate"])

    # -- device block ops -------------------------------------------------
    def _copy_block(self, src: int, dst: int):
        """Copy physical block src → dst across every layer's k/v pool
        (the CoW move). Host-driven between ticks — the tick program
        itself never writes a shared block, so this is the ONLY writer
        that can touch one, and it only reads it."""
        for name in self.cache_names:
            arr = self.scope.get(name)
            if hasattr(arr, "at"):               # jax array
                arr = arr.at[dst].set(arr[src])
            else:
                arr = np.asarray(arr)
                arr[dst] = arr[src]
            self.scope.set_var(name, arr)

    def stats(self) -> Dict:
        s = super().stats()
        s["pager"] = self.pager.stats()
        s["paged_attention_lowering"] = self.paged_attention_lowering
        s["kv_quant"] = {"enabled": self.kv_quant,
                         "freed_bytes": self.kv_quant_freed_bytes}
        s["block_bytes"] = self.block_bytes
        if self.model.window:
            # the second pool, of the sliding-window layers
            s["window_pool"] = dict(
                s["pager"]["window"], block_bytes=self.window_block_bytes,
                pool_bytes=self.window_block_bytes * self.n_window_blocks,
                request_bound=self.pager.window_bound(self.chunk_tokens))
        if self.n_snapshots:
            # the second kind of state, too large for a snapshot a block: a
            # copy a slot, and the pool's entries (Mamba-2's `h` or the delta
            # rule's `S`: one seam, one key, which keeps its first name)
            s["ssm_state"] = dict(
                s["pager"]["snapshot_pool"],
                # the layers that hold it, and those of them that hold K/V
                # rows too (a layer whose mixer is both)
                layers=len(self.model.recurrent_layers),
                layers_with_kv=len(set(self.model.recurrent_layers)
                                   & set(self.model.attention_layers)),
                bytes_per_copy=self.state_bytes,
                slot_bytes=self.state_bytes * self.n_slots,
                pool_bytes=self.state_bytes * self.n_snapshots)
        elif self.state_bytes:
            # the second kind of state: a copy a slot, a snapshot a block
            s["conv_state"] = dict(
                self.pager.stats()["block_state"],
                bytes_per_copy=self.state_bytes,
                slot_bytes=self.state_bytes * self.n_slots,
                snapshot_bytes=self.state_bytes * self.n_blocks)
        if self.model.attention == "latent":
            lat = self.model.latent
            s["latent_row"] = {"values": lat.row_values,
                               "stored": lat.row_lanes}
        if self.model.moe is not None:
            # per routed layer, the rows each held expert got since
            # construction (in `held`'s order)
            s["expert_rows"] = {"layers": list(self.model.moe_layers),
                                "held": list(self.model.moe.held),
                                "rows": self.expert_rows.tolist()}
        if self.host_tier is not None:
            # measured wire bytes (actual buffer sizes the stream moved)
            # next to the per-block figure the prediction side uses —
            # tests/test_offload.py asserts they reconcile EXACTLY
            s["offload"] = {
                "d2h_bytes": self.ht_d2h_bytes,
                "h2d_bytes": self.ht_h2d_bytes,
                "per_block_bytes": self._ht_per_block_bytes,
                "suspended": len(self._ht_queue),
            }
        return s


def paged_beam_search(engine: PagedKVEngine, prompt: Sequence[int],
                      max_new: int, beam_size: int,
                      eos_id: Optional[int] = None
                      ) -> List[Tuple[List[int], float]]:
    """Beam search through a PagedKVEngine's compiled tick, with the
    beams' common prefix held ONCE in the block pool.

    The prompt prefills a single hypothesis; the fork into `beam_size`
    beams shares every fully-written block by refcount and copy-on-
    writes the partial divergence block (`KVPager.fork`). Each decode
    tick runs all live beams as independent tick slots; the tick's
    top-k log-probs (engine built with `topk_k >= beam_size`) score the
    beam_size × k candidate extensions on the host, and every parent
    that survives in more than one child is forked again — CoW at the
    new divergence block. Beams that emit `eos_id` retire with their
    score frozen.

    Prefix sharing composes transparently: a cached prefix (from an
    earlier request, or a previous beam call with the same prompt)
    short-circuits the prefill exactly as in greedy serving, and the
    result is token-identical either way — shared blocks hold byte-
    identical K/V because compute is deterministic (pinned by
    tests/test_kv_pager.py).

    Returns [(tokens, cumulative log-prob)] sorted best-first,
    `beam_size` entries. The engine must be idle — beam decode owns
    every tick slot while it runs."""
    enforce(isinstance(engine, PagedKVEngine),
            "paged_beam_search needs a PagedKVEngine",
            exc=InvalidArgumentError)
    enforce(engine.topk_k >= beam_size,
            f"engine was built with topk_k={engine.topk_k}; beam_size="
            f"{beam_size} needs topk_k >= beam_size",
            exc=InvalidArgumentError)
    enforce(beam_size >= 1 and beam_size <= engine.n_slots,
            f"beam_size {beam_size} must fit the engine's "
            f"{engine.n_slots} tick slots", exc=InvalidArgumentError)
    enforce(engine.n_active == 0 and engine.n_pending == 0,
            "paged_beam_search needs an idle engine (it owns every "
            "tick slot)", exc=InvalidArgumentError)
    prompt = [int(t) for t in prompt]
    max_new = int(max_new)
    enforce(len(prompt) >= 1 and max_new >= 1,
            "need a non-empty prompt and max_new >= 1",
            exc=InvalidArgumentError)
    engine._enforce_request_fits(prompt, max_new)
    pager, bs, P = engine.pager, engine.block_size, len(prompt)
    need_len = min(P + max_new, engine.max_len)

    root = pager.try_admit(prompt, need_len)
    enforce(root is not None,
            "block pool exhausted (even after eviction) — cannot admit "
            "the beam root", exc=InvalidArgumentError)

    feeds = engine._feeds

    def _zero():
        for a in feeds.values():
            a[:] = 0

    def _tick(slots):
        """slots: {slot: (tok, pos, table)} — run one compiled tick,
        return (topk_logp [S,1,k], topk_ids [S,1,k]) as numpy."""
        _zero()
        san = pager.sanitizer
        for slot, (tok, pos, table) in slots.items():
            feeds["tick_tok"][slot, 0] = tok
            feeds["tick_pos"][slot, 0, 0] = float(pos)
            feeds["tick_btab"][slot, :len(table.blocks)] = table.blocks
            lb, off = divmod(pos, bs)
            feeds["tick_wblock"][slot] = table.blocks[lb]
            feeds["tick_woff"][slot] = off
            if san is not None:
                # beam writes ride the CoW contract too: each live
                # hypothesis must own its write block exclusively
                san.note_write(table, pos)
        out = engine._step.run(feeds)
        # run() re-pointed the main step's bound rw tuple at the live
        # cache arrays — a co-resident speculative verify step must
        # refresh before it next runs
        engine._target_state_owner = "main"
        engine.n_ticks += 1
        engine.last_tick_at = time.time()
        return np.asarray(out[1]), np.asarray(out[2])

    # -- prefill the root hypothesis through slot 0 (shared span skipped)
    logp = ids = None
    for pos in range(root.shared_len, P):
        logp, ids = _tick({0: (prompt[pos], pos, root)})
        if (pos + 1) % bs == 0:
            pager.note_block_filled(root, pos // bs, prompt)

    # -- fork the root into beam_size hypotheses (CoW at the partial
    #    block; with P % bs == 0 the fork is pure sharing, zero copies)
    beams = []
    for b in range(beam_size):
        table = pager.fork(root, P, engine._copy_block)
        tok = int(ids[0, 0, b])
        beams.append({"table": table, "tokens": [tok], "next_tok": tok,
                      "score": float(logp[0, 0, b]), "alive": True})
    pager.release(root)
    finished: List[Dict] = []
    for beam in beams:
        if eos_id is not None and beam["next_tok"] == eos_id:
            beam["alive"] = False
            finished.append(beam)
    beams = [b_ for b_ in beams if b_["alive"]]

    # -- decode: all live beams per tick, host-side candidate selection
    for g in range(1, max_new):
        if not beams:
            break
        slots = {i: (beam["next_tok"], P - 1 + g, beam["table"])
                 for i, beam in enumerate(beams)}
        logp, ids = _tick(slots)
        cands = []
        for i, beam in enumerate(beams):
            for j in range(beam_size):
                cands.append((beam["score"] + float(logp[i, 0, j]),
                              i, int(ids[i, 0, j])))
        cands.sort(key=lambda c: c[0], reverse=True)
        cands = cands[:len(beams)]
        # fork parents that survive in >1 child; retire the childless.
        # Forks run BEFORE any child's next write, so the parent's
        # blocks still hold exactly the shared history (written_len =
        # P + g positions).
        n_children = {}
        for _, i, _t in cands:
            n_children[i] = n_children.get(i, 0) + 1
        new_beams = []
        taken = {}
        for score, i, tok in cands:
            parent = beams[i]
            taken[i] = taken.get(i, 0) + 1
            if taken[i] < n_children[i]:
                table = pager.fork(parent["table"], P + g,
                                   engine._copy_block)
            else:
                table = parent["table"]      # last child inherits
            nb = {"table": table, "tokens": parent["tokens"] + [tok],
                  "next_tok": tok, "score": score, "alive": True}
            new_beams.append(nb)
        for i, beam in enumerate(beams):
            if i not in n_children:
                pager.release(beam["table"])
        beams = []
        for nb in new_beams:
            if eos_id is not None and nb["next_tok"] == eos_id:
                nb["alive"] = False
                finished.append(nb)
            else:
                beams.append(nb)

    finished.extend(beams)
    for beam in finished:
        if beam["table"].blocks:
            pager.release(beam["table"])
    _zero()
    finished.sort(key=lambda b_: b_["score"], reverse=True)
    return [(beam["tokens"], beam["score"])
            for beam in finished[:beam_size]]
