"""Paged KV-cache block manager with automatic prefix caching
(reference-era analog: vLLM's BlockManager + its hash-based prefix cache,
`vllm/core/block_manager.py` — the PagedAttention half of iteration-level
scheduling).

The physical KV cache is a fixed pool of `num_blocks` blocks of
`block_size` token slots each (the engine owns the actual [L, NB, BS, H*Dh]
arrays; this class owns only the *map*). Each live sequence holds an ordered
block table — logical token position `p` lives in physical block
`table[p // block_size]` at offset `p % block_size`.

Prefix caching: every FULL block whose KV has been computed is registered
under a content hash CHAINED over token ids (block i's key commits to every
token in blocks 0..i, so two sequences share a block only when their entire
prefixes match). Blocks are refcounted; `allocate_cached` walks a new
prompt's chain through the hash index and reuses every leading hit — the
prefill skips straight to the first cold block. Freed blocks whose content
is registered are RETAINED on an LRU "cached" list instead of being blanked:
they serve future hits, yet remain reclaimable — the free list exhausting
falls back to evicting the coldest cached block. Admission math
(`can_allocate` / `free_blocks`) therefore counts blank + cached blocks;
`KVStats.utilization` counts only live (referenced) blocks.

Tiered cache (the cluster-wide half, PR 12): with a `host_tier`
(`kv_tier.HostKVTier`) attached, an HBM eviction SAVES the block's bytes to
host RAM instead of killing the content — the manager queues (hash, block)
save orders the engine drains (`drain_saves`) before the block is
overwritten, and `allocate_cached` consults the tier on an index miss:
a tier hit acquires a fresh block, re-registers the hash, and queues a
(hash, block, bytes, remote) LOAD (`drain_loads`) the engine applies to
the HBM arrays before its next kernel launch. `adopt_block` is the same mechanism
driven by a REMOTE import (`engine.import_blocks`): blocks computed by a
prefill-pool replica land here as cached entries. The manager stays a pure
map — every byte move is drained by the engine at a step boundary, ordered
saves -> COW -> loads -> kernels so evicted bytes are read before anything
overwrites them. Hot-hash digest entries survive HBM eviction while the
bytes remain host-resident (the fleet router keeps steering matching
prompts here, where the import is a host-RAM copy, not a recompute).

Invariants (enforced by `check_invariants`):
  * every block is blank (free list) XOR cached (ref 0, content retained)
    XOR live (ref >= 1) — never two at once, none lost;
  * a block's refcount equals its number of table references;
  * a refcounted-shared block is NEVER written in place: extending a
    sequence into a shared block forks it copy-on-write — the manager
    rewrites the table and queues a (src, dst) physical copy for the engine
    (`drain_cow`); only full, immutable blocks are ever hash-shared.

Admission control rides on `can_allocate`: the scheduler refuses (queues,
never crashes) a prefill whose prompt + first token doesn't fit
blank + reclaimable blocks, and preempts the youngest running sequence when
decode growth hits the budget mid-flight.

Block 0 is RESERVED as the null/scratch block: the engine pads decode
batches to bucket shapes by pointing dummy lanes' block tables at block 0,
so their writes land somewhere harmless. It is never handed out.

Layers of two kinds over ONE pool (`group_windows`, from the model's
`kv_layout`): a model with global and sliding-window layers deals its layers
into G groups of equal size, each of one kind, and the pool is
[layers a group, NB, BS, row]: a block has the same bytes whichever group
holds it, there is one `num_blocks` and one free list, and nothing divides
memory between the kinds. A sequence has one block table a GROUP, all
`blocks_for(len)` long. A global group holds a block for every token. A
window group (window w) holds only the blocks a future query can still see:
before a program whose first query sits at position q runs, `slide` (and
`grow`, for decode) RELEASES every block wholly below q - w + 1 and acquires
the blocks up to the last position the program writes; a released or not yet
acquired entry is the null block, which the window mask and the causal mask
keep out of every sum. At rest a lane past the window therefore holds, in a
window group, at most blocks_for(w) + 1 blocks (while a prefill chunk is in
flight, the chunk's blocks more). Admission asks the pool for what the
sequence will hold at rest (`blocks_needed`) and acquires a window group's
blocks as the prefill reaches them, so `can_allocate`, `fits_ever`,
`free_blocks`, preemption and `KVStats` all count what is really held.

Prefix reuse with groups, the simplest sound rule: a full block of tokens is
registered under its chained hash as ONE entry naming G physical blocks, one
a group, and is reusable only while EVERY group's copy still stands. A
window group's released block rests on the cached list like a finished
sequence's (its rows are still that prefix's K/V); the moment any one copy
is reclaimed for new content the whole entry leaves the index and its other
copies lose their registration, so a prefix hit can never hand out a block
whose rows were overwritten. `fork`, the host tier, `adopt_block` and
`export_sources` are the one-group manager's: with G > 1 they refuse.

State a SEQUENCE (`state_slots`, from the model's `kv_layout`): a model whose
layers keep one fixed state a sequence beside (or in place of) rows a token
brings a second counted resource, the state slot. A sequence claims one with
its first allocation (`allocate_cached`), holds it while it lives and gives
it back with `free`, so a preempted sequence's slot goes with its blocks and
its readmission claims another, whose state the program starts from zero.
`can_allocate`, `fits_ever`, `stats` and `check_invariants` count slots beside
blocks. Slot 0 is the null slot (padding lanes), never handed out. A cached
block holds the row layers' rows and NOTHING of the state at that boundary,
so for such a model a prefix hit would be wrong: the manager runs with the
index off whatever `enable_prefix_caching` says (`allocate_cached` reports no
cached token, `register_computed` registers no block, nothing is hashed),
and `fork` refuses. Snapshots of the state at block boundaries would lift
both.
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple


class KVCacheExhausted(RuntimeError):
    """Raised by allocate/grow when blank + evictable blocks cannot cover
    the request.

    The scheduler treats this as back-pressure (requeue/preempt), never as a
    crash — it reaches user code only on programming errors (e.g. a prompt
    longer than the whole pool, which `fits_ever` screens at submit)."""


def _chain_hash(prev: bytes, tokens: Sequence[int]) -> bytes:
    """Content key of one full block given its predecessor's key — collision
    resistance matters (a collision would silently serve another prompt's
    KV), so this is a real hash, not Python's."""
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(b",".join(str(int(t)).encode() for t in tokens))
    return h.digest()


# Wire width of one hot-prefix digest entry (`prefix_digest`): the fleet
# router only needs to DISCRIMINATE prefixes (a truncation collision routes
# to a replica that turns out to miss — no correctness impact), so digests
# ship 8 of the 16 hash bytes. `serve/fleet/routing.py` derives its routing
# keys with the same truncation.
DIGEST_HASH_BYTES = 8

# Hot-prefix hashes retained for digest export (recency-ordered).
_HOT_CAP = 512


@dataclasses.dataclass(frozen=True)
class KVStats:
    num_blocks: int          # allocatable blocks (excludes the null block)
    free_blocks: int         # allocatable NOW: blank + reclaimable cached
    used_blocks: int         # referenced by >= 1 live sequence
    cached_blocks: int       # ref == 0 but content retained (subset of free)
    num_seqs: int
    utilization: float       # LIVE fraction of the pool, 0..1
    hits: int = 0            # full blocks reused from the prefix cache
    misses: int = 0          # cacheable full blocks that had to be computed
    evictions: int = 0       # cached blocks reclaimed for new allocations
    cow_copies: int = 0      # copy-on-write forks of shared blocks
    host_hits: int = 0       # hits served from the host-RAM tier (subset)
    host_blocks: int = 0     # blocks resident in the host tier
    host_bytes: int = 0      # bytes resident in the host tier
    state_slots: int = 0     # state slots a model with state has (null slot apart)
    state_slots_held: int = 0  # held by live sequences


class KVBlockManager:
    """Refcounting free-list allocator mapping sequence ids to ordered block
    tables, with a chained-hash prefix cache over full blocks."""

    NULL_BLOCK = 0

    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        enable_prefix_caching: bool = True,
        host_tier=None,
        group_windows: Sequence[int] = (0,),
        state_slots: int = 0,
    ):
        self.group_windows = tuple(int(w) for w in group_windows)
        if not self.group_windows or min(self.group_windows) < 0:
            raise ValueError(f"bad group_windows {group_windows!r}")
        self._G = len(self.group_windows)
        self._sliding = any(self.group_windows)
        if self._G > 1 and host_tier is not None:
            raise ValueError(
                "the host KV tier holds blocks of one group; a model whose "
                f"layers form {self._G} KV groups runs with it off")
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.block_size = block_size
        self.num_blocks = num_blocks
        # State slots 1..state_slots (0: the model keeps no state a sequence).
        self.state_slots = int(state_slots)
        self._free_states: List[int] = list(range(self.state_slots, 0, -1))
        self._state_of: Dict[str, int] = {}
        self.states_claimed = 0
        self.states_released = 0
        # A model with state runs with the index off (module docstring).
        self.caching = enable_prefix_caching and not self.state_slots
        # Host-RAM tier below HBM (kv_tier.HostKVTier, None = off). Accessed
        # only under the engine lock, like every other mutation here.
        self._tier = host_tier if enable_prefix_caching else None
        if self._tier is not None:
            self._tier.on_evict = self._on_tier_evict
        # Block 0 reserved; LIFO free list so recently-freed (cache-warm)
        # blocks are reused first.
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        # ref == 0 blocks whose content is still registered: insertion order
        # is recency (oldest first = LRU eviction order).
        self._cached: "OrderedDict[int, None]" = OrderedDict()
        self._ref: Dict[int, int] = {}            # live blocks only
        # One table a group, each blocks_for(len) long; NULL_BLOCK where a
        # window group released the block or has not acquired it yet.
        self._tables: Dict[str, List[List[int]]] = {}
        self._lens: Dict[str, int] = {}           # tokens stored per sequence
        self._held_lo: Dict[str, List[int]] = {}  # first held index, a group
        self._hash_of: Dict[int, bytes] = {}      # registered block -> key
        # key -> the canonical blocks, one a group
        self._index: Dict[bytes, Tuple[int, ...]] = {}
        self._chain: Dict[str, List[bytes]] = {}  # per-seq registered keys
        # Recency-ordered registered/hit hashes (hottest LAST): the bounded
        # hot-prefix digest the fleet router steers by. Advisory only —
        # entries die with their index entry on eviction.
        self._hot: "OrderedDict[bytes, None]" = OrderedDict()
        # (src, dst) physical copies the ENGINE must apply before the next
        # kernel launch — the manager owns only the map.
        self._pending_copies: List[Tuple[int, int]] = []
        # block -> (hash, bytes, remote): tier/import content the engine
        # must land in the HBM arrays before its next kernel launch
        # (drain_loads); `remote` marks content adopted from ANOTHER
        # replica's export (adopt_block) vs a local host-tier re-admission
        # — the engine's import counter tracks only the former. An eviction
        # of a pending-load block just drops the entry — the bytes never
        # reached HBM, so there is nothing to save and the index entry dies
        # with it.
        self._pending_loads: Dict[int, Tuple[bytes, object, bool]] = {}
        # (hash, block): evicted registered blocks whose bytes the engine
        # must copy OUT to the host tier before anything overwrites them
        # (drain_saves runs FIRST in the engine's step-top drain order).
        self._pending_saves: List[Tuple[bytes, int]] = []
        # Landed watermark per sequence: tokens whose KV is KNOWN computed
        # (prefix-cache hits at admission + every register_computed
        # notification). Lags the true cursor by at most the notification
        # granularity; `fork` trims the child to it so a speculatively
        # over-allocated parent can never leak an un-COWed shared tail.
        self._landed: Dict[str, int] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.cow_copies = 0
        self.host_hits = 0
        self.window_released = 0   # blocks window groups gave back while live
        # Live blocks that stand in a window group's table (a block is in
        # tables of one group at a time: an index entry names one a group).
        self._window_live: set = set()

    # ------------------------------------------------------------- queries
    @property
    def free_blocks(self) -> int:
        """Blocks allocatable right now (blank + evictable cached)."""
        return len(self._free) + self._evictable()

    def _evictable(self) -> int:
        # Cached blocks that are the source of a still-pending COW copy must
        # survive until the engine applies it; they drop out of the
        # reclaimable count until drain_cow().
        if not self._pending_copies:
            return len(self._cached)
        protected = {s for s, _ in self._pending_copies}
        return sum(1 for b in self._cached if b not in protected)

    def blocks_for(self, num_tokens: int) -> int:
        return -(-num_tokens // self.block_size)  # ceil div

    def _span(self, window: int, first_query: int, upto: int) -> Tuple[int, int]:
        """[lo, hi): the block indices a group of `window` (0 = global)
        holds when the next query sits at `first_query` and positions below
        `upto` are covered."""
        lo = max(0, first_query - window + 1) // self.block_size if window else 0
        return lo, max(lo, self.blocks_for(upto))

    def blocks_needed(self, num_tokens: int) -> int:
        """Blocks a sequence of `num_tokens` holds at rest, over all groups:
        every token's in a global group, the window and one block in a
        window group. What admission asks the pool for: a window group
        acquires its blocks as the prefill reaches them (`slide`), but a
        prompt is admitted only when the pool could give them all."""
        nb = self.blocks_for(num_tokens)
        return sum(min(nb, self.blocks_for(w) + 1) if w else nb
                   for w in self.group_windows)

    def _state_free(self) -> bool:
        """A state slot is to be had (or the model keeps no state)."""
        return not self.state_slots or bool(self._free_states)

    def can_allocate(self, num_tokens: int) -> bool:
        return self.blocks_needed(num_tokens) <= self.free_blocks and self._state_free()

    def fits_ever(self, num_tokens: int) -> bool:
        """Could this many tokens fit an EMPTY pool? (submit-time sanity)"""
        return self.blocks_needed(num_tokens) <= self.num_blocks - 1

    @property
    def state_slots_held(self) -> int:
        return len(self._state_of)

    @property
    def blocks_held(self) -> int:
        """Blocks some live sequence references (`KVStats.used_blocks`)."""
        return len(self._ref)

    @property
    def window_blocks_held(self) -> int:
        """Of them, those that stand in a window group's table."""
        return len(self._window_live)

    def state_slot(self, seq_id: str) -> int:
        """The state slot `seq_id` holds (a model with state)."""
        return self._state_of[seq_id]

    def block_table(self, seq_id: str) -> List[int]:
        """The first group's table (THE table of a one-group model; with
        groups, the one whose length is the sequence's width)."""
        return list(self._tables[seq_id][0])

    def block_tables(self, seq_id: str) -> List[List[int]]:
        """One table a group, all the same length."""
        return [list(t) for t in self._tables[seq_id]]

    def held_blocks(self, seq_id: str) -> List[int]:
        """Blocks really held, a group."""
        return [sum(1 for b in t if b != self.NULL_BLOCK)
                for t in self._tables[seq_id]]

    def seq_len(self, seq_id: str) -> int:
        return self._lens[seq_id]

    def num_registered(self, seq_id: str) -> int:
        """Full blocks of `seq_id` already in the prefix index — the
        scheduler's cheap check for whether registration has blocks to
        catch up on (multi-token speculative appends can jump PAST a block
        boundary, so an exact `landed % block_size == 0` test misses)."""
        return len(self._chain.get(seq_id, ()))

    def _touch_hot(self, h: bytes) -> None:
        self._hot[h] = None
        self._hot.move_to_end(h)
        while len(self._hot) > _HOT_CAP:
            self._hot.popitem(last=False)

    def prefix_digest(self, max_entries: int = 64) -> List[str]:
        """Bounded digest of the HOTTEST prefix hashes (truncated hex,
        hottest first) — piggybacked on controller telemetry so fleet
        routers can steer prompts toward the replica already holding their
        prefix. Empty when prefix caching is off."""
        if not self.caching or max_entries < 1:
            return []
        out = []
        for h in reversed(self._hot):
            out.append(h[:DIGEST_HASH_BYTES].hex())
            if len(out) >= max_entries:
                break
        return out

    def stats(self) -> KVStats:
        total = self.num_blocks - 1
        live = len(self._ref)
        return KVStats(
            num_blocks=total,
            free_blocks=len(self._free) + self._evictable(),
            used_blocks=live,
            cached_blocks=len(self._cached),
            num_seqs=len(self._tables),
            utilization=live / total if total else 0.0,
            hits=self.hits,
            misses=self.misses,
            evictions=self.evictions,
            cow_copies=self.cow_copies,
            host_hits=self.host_hits,
            host_blocks=self._tier.blocks if self._tier is not None else 0,
            host_bytes=self._tier.bytes_used if self._tier is not None else 0,
            state_slots=self.state_slots,
            state_slots_held=self.state_slots_held,
        )

    # ------------------------------------------------------- block plumbing
    def _acquire(self) -> int:
        """One blank block: the free list first, then LRU-evict the coldest
        cached block. Without a host tier the evictee's index entry dies
        with it; with one, its bytes are queued to SAVE into host RAM (the
        engine drains before anything overwrites the block) and its hot-hash
        digest entry survives — the fleet router keeps steering matching
        prompts here, where `allocate_cached`'s tier consult makes the
        re-admission a host-RAM copy instead of a recompute."""
        if self._free:
            return self._free.pop()
        protected = {s for s, _ in self._pending_copies}
        for b in self._cached:
            if b not in protected:
                del self._cached[b]
                h = self._hash_of.pop(b)
                for other in self._index.pop(h):
                    # The entry is gone for every group: a copy that rests
                    # cached is blank now, a live one just unregistered.
                    if other != b and self._hash_of.pop(other, None) is not None \
                            and other in self._cached:
                        del self._cached[other]
                        self._free.append(other)
                self.evictions += 1
                pending = self._pending_loads.pop(b, None)
                if self._tier is not None and pending is None:
                    # Bytes are in HBM and about to be reused: save them to
                    # the host tier (skip when the tier already holds them).
                    if not self._tier.contains(h):
                        self._pending_saves.append((h, b))
                    # Host-resident content stays advertised (hot entry
                    # kept); the tier's own eviction drops it for real.
                elif pending is not None and self._tier is not None \
                        and self._tier.contains(h):
                    pass  # bytes still live in the tier — stay advertised
                else:
                    self._hot.pop(h, None)
                return b
        raise KVCacheExhausted("KV pool exhausted (no blank or evictable blocks)")

    def _on_tier_evict(self, h: bytes) -> None:
        """Host-tier budget eviction: the content is now gone everywhere
        below the fleet — stop advertising it (unless it is independently
        registered in HBM)."""
        if h not in self._index:
            self._hot.pop(h, None)

    def _incref(self, b: int) -> None:
        if b in self._ref:
            self._ref[b] += 1
        else:  # reviving a cached (ref 0) block
            del self._cached[b]
            self._ref[b] = 1

    def _release_one(self, b: int) -> None:
        r = self._ref[b] - 1
        if r > 0:
            self._ref[b] = r
            return
        del self._ref[b]
        self._window_live.discard(b)
        if b in self._hash_of:
            # Content stays findable: most-recently-freed lands at the LRU
            # tail, so eviction takes the coldest prefix first.
            self._cached[b] = None
        else:
            assert b != self.NULL_BLOCK and b not in self._free, (
                f"block {b} double-freed"
            )
            self._free.append(b)

    # --------------------------------------------------------- allocation
    def allocate(self, seq_id: str, num_tokens: int) -> List[int]:
        """Claim blocks for a new sequence of `num_tokens` tokens, with no
        cache lookup (token ids unknown). Raises KVCacheExhausted when
        blank + evictable blocks can't cover it (the caller keeps the
        request queued) and ValueError on reuse of a live seq_id."""
        table, _ = self.allocate_cached(seq_id, None, num_tokens)
        return table

    def allocate_cached(
        self,
        seq_id: str,
        token_ids: Optional[Sequence[int]],
        num_tokens: int,
    ) -> Tuple[List[int], int]:
        """Claim blocks for a new sequence, reusing every leading full block
        whose chained content hash is already registered.

        `token_ids` is the prompt (length <= num_tokens; the surplus covers
        generated tokens). Returns (block_table, cached_tokens):
        `cached_tokens` prompt positions already hold valid KV — the prefill
        starts at that offset. At least one prompt token is always left cold
        so the engine has a real position to read next-token logits from.
        """
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already has an allocation")
        if num_tokens < 1:
            raise ValueError("allocate needs >= 1 token")
        if token_ids is not None and len(token_ids) > num_tokens:
            raise ValueError("token_ids longer than the allocation")
        if not self._state_free():
            raise KVCacheExhausted("every state slot is held")
        nb = self.blocks_for(num_tokens)
        # Chain walk: per leading full block, an HBM index hit ("idx",
        # blocks, one a group), a host-tier hit ("tier", h, bytes) — acquired
        # below and loaded by the engine before its next kernel — or a miss
        # (walk ends).
        walk: List[Tuple] = []
        chain: List[bytes] = []
        if self.caching and token_ids is not None and len(token_ids) > 1:
            # Cap: never serve the WHOLE prompt from cache — the last
            # position must be recomputed to produce first-token logits.
            cacheable = (len(token_ids) - 1) // self.block_size
            prev = b""
            for i in range(cacheable):
                h = _chain_hash(
                    prev,
                    token_ids[i * self.block_size:(i + 1) * self.block_size],
                )
                blocks = self._index.get(h)
                if blocks is not None:
                    walk.append(("idx", blocks))
                elif self._tier is not None:
                    blob = self._tier.get(h)  # touches the tier's LRU
                    if blob is None:
                        break
                    walk.append(("tier", h, blob))
                else:
                    break
                chain.append(h)
                self._touch_hot(h)
                prev = h
            self.hits += len(walk)
            self.host_hits += sum(1 for w in walk if w[0] == "tier")
            self.misses += cacheable - len(walk)
        cached_tokens = len(walk) * self.block_size
        # What each group holds from the start: every block (global), or
        # (window) the blocks from the first computed position's window up
        # to that position; `slide` acquires the rest as the prefill reaches
        # them. Hits outside a window group's span are not taken.
        spans = [
            self._span(w, cached_tokens, cached_tokens + 1 if w else num_tokens)
            for w in self.group_windows
        ]
        idx_hits = [
            w[1][g] for i, w in enumerate(walk) if w[0] == "idx"
            for g, (lo, hi) in enumerate(spans) if lo <= i < hi
        ]
        # Hits currently resting on the cached list are about to be revived —
        # they can't double as eviction fodder for our own fresh blocks
        # (COW-protected ones were never counted evictable to begin with).
        # Tier hits and cold blocks both need a real acquisition.
        protected = {s for s, _ in self._pending_copies}
        reviving = sum(
            1 for b in idx_hits
            if b not in self._ref and b not in protected
        )
        # Asked of the pool: what the sequence holds at rest (`blocks_needed`,
        # which is exactly the acquisition when no group slides).
        need_new = max(sum(hi - lo for lo, hi in spans),
                       self.blocks_needed(num_tokens)) - len(idx_hits)
        if need_new > len(self._free) + self._evictable() - reviving:
            raise KVCacheExhausted(
                f"{need_new} blocks needed, "
                f"{len(self._free) + self._evictable() - reviving} available"
            )
        # Revive/share EVERY index hit first: a fresh acquisition below
        # may evict from the cached list, and a hit resting there must not
        # be its victim.
        for b in idx_hits:
            self._incref(b)
        tables: List[List[int]] = []
        for g, (lo, hi) in enumerate(spans):
            table = [self.NULL_BLOCK] * nb
            for i in range(lo, hi):
                w = walk[i] if i < len(walk) else None
                if w is not None and w[0] == "idx":
                    table[i] = w[1][g]
                    continue
                fresh = self._acquire()
                self._ref[fresh] = 1
                table[i] = fresh
                if w is not None:       # a tier hit (one-group manager only)
                    _, h, blob = w
                    self._index[h] = (fresh,)
                    self._hash_of[fresh] = h
                    self._pending_loads[fresh] = (h, blob, False)
            tables.append(table)
            if self.group_windows[g]:
                self._window_live.update(table[lo:hi])
        self._tables[seq_id] = tables
        self._held_lo[seq_id] = [lo for lo, _ in spans]
        self._lens[seq_id] = num_tokens
        self._chain[seq_id] = chain
        self._landed[seq_id] = cached_tokens
        if self.state_slots:
            self._state_of[seq_id] = self._free_states.pop()
            self.states_claimed += 1
        return list(tables[0]), cached_tokens

    def fork(self, parent_id: str, child_id: str) -> List[int]:
        """Share `parent_id`'s table up to its LANDED watermark with a new
        sequence (beam / n-best style). Shared blocks incref; whichever
        sequence later extends into a shared partial block triggers
        copy-on-write there.

        The child is TRIMMED to the parent's landed watermark (tokens whose
        KV is known computed: admission cache hits + every
        `register_computed` notification): a parent carrying a SPECULATIVE
        over-allocation (`_lens` grown past the landed watermark to fund
        drafts the verify step may reject) must not hand the child slots
        whose content is undefined — grow()'s COW check keys off `_lens`,
        so an un-trimmed child writing below the over-allocated tail would
        miss its copy (the PR 7 caveat, now handled instead of documented).
        The watermark lags true compute by at most the notification
        granularity; the trimmed tail is re-derivable (the child recomputes
        or re-hits it). A parent allocated via plain `allocate()` (token
        ids unknown) that was never advanced by `grow(..., num_computed=)`
        or `register_computed` has watermark 0 and shares NOTHING — the
        manager cannot tell its content from speculative garbage."""
        if self._G > 1:
            raise NotImplementedError("fork of a sequence over several KV groups")
        if self.state_slots:
            raise NotImplementedError(
                "fork of a sequence with state: the shared blocks hold nothing of "
                "the state at their boundary (no snapshot is kept)")
        if child_id in self._tables:
            raise ValueError(f"sequence {child_id!r} already has an allocation")
        table = self._tables[parent_id][0]  # KeyError = unknown parent
        landed = self._landed.get(parent_id, 0)
        keep = min(self.blocks_for(landed), len(table))
        shared = table[:keep]
        for b in shared:
            self._incref(b)
        self._tables[child_id] = [list(shared)]
        self._held_lo[child_id] = [0]
        self._lens[child_id] = min(landed, self._lens[parent_id])
        chain = self._chain.get(parent_id, ())
        self._chain[child_id] = list(chain[:keep])
        self._landed[child_id] = self._lens[child_id]
        return list(shared)

    def grow(
        self,
        seq_id: str,
        new_len: int,
        token_ids: Optional[Sequence[int]] = None,
        num_computed: Optional[int] = None,
        first_query: Optional[int] = None,
    ) -> List[int]:
        """Extend `seq_id`'s table to cover `new_len` tokens (decode append).

        If the next write position falls inside a SHARED block (fork), that
        block is forked copy-on-write first: the table is rewritten and a
        (src, dst) physical copy is queued for `drain_cow`. With `token_ids`
        (the sequence's full token list) and `num_computed` (tokens whose KV
        is actually written), newly-completed full blocks are registered in
        the prefix index. Returns the (possibly extended) block table;
        KVCacheExhausted when the pool is dry — the scheduler preempts.

        `new_len` below the current coverage is a no-op on the table
        (registration still runs): a speculative grow funds draft slots the
        verify step may reject, so the NEXT step legitimately asks for less
        than the table already covers.

        With window groups, `first_query` is the position of the first
        token the coming step computes (default: `num_computed`, else the
        last covered position): after the global groups grew and the full
        blocks were registered, each window group slides (`slide`)."""
        tables = self._tables[seq_id]
        cur = self._lens[seq_id]
        if new_len < cur:
            new_len = cur
        need = self.blocks_for(new_len) - len(tables[0])
        wi = cur // self.block_size      # block the next write lands in
        keeps_all = [g for g, w in enumerate(self.group_windows) if not w]
        need_cow = int(
            self._G == 1 and wi < len(tables[0])
            and self._ref.get(tables[0][wi], 0) > 1
        )
        if need * len(keeps_all) + need_cow > len(self._free) + self._evictable():
            raise KVCacheExhausted(
                f"{need * len(keeps_all) + need_cow} blocks needed, "
                f"{len(self._free) + self._evictable()} free"
            )
        if need_cow:
            table = tables[0]
            src = table[wi]
            dst = self._acquire()
            self._ref[dst] = 1
            self._pending_copies.append((src, dst))
            table[wi] = dst
            self._release_one(src)   # still held by the other owner(s)
            self.cow_copies += 1
        for g, table in enumerate(tables):
            for _ in range(need):
                if g in keeps_all:
                    nb = self._acquire()
                    self._ref[nb] = 1
                    table.append(nb)
                else:
                    table.append(self.NULL_BLOCK)   # `slide` fills its span
        self._lens[seq_id] = new_len
        if token_ids is not None and num_computed is not None:
            self.register_computed(seq_id, token_ids, num_computed)
        if self._sliding:
            if first_query is None:
                first_query = num_computed if num_computed is not None else cur - 1
            self.slide(seq_id, first_query, new_len)
        return list(tables[0])

    def slide(self, seq_id: str, first_query: int, upto: int) -> int:
        """Move `seq_id`'s window groups forward, before a program whose
        first query sits at position `first_query` and which writes
        positions below `upto`: every block wholly behind the window of
        `first_query` is RELEASED (no later query can see it; a registered
        one rests on the cached list, its rows still that prefix's), then
        the blocks up to `upto` are acquired. The releases stand even when
        the acquisition raises KVCacheExhausted (the scheduler preempts and
        asks again). Returns the blocks released. Nothing to do for a model
        without window groups."""
        if not self._sliding:
            return 0
        released = self._release_behind(seq_id, first_query)
        tables, held_lo = self._tables[seq_id], self._held_lo[seq_id]
        upto = min(upto, self._lens[seq_id])
        want = []
        for g, w in enumerate(self.group_windows):
            if w:
                _, hi = self._span(w, first_query, upto)
                want += [(tables[g], i) for i in range(held_lo[g], hi)
                         if tables[g][i] == self.NULL_BLOCK]
        if len(want) > len(self._free) + self._evictable():
            raise KVCacheExhausted(
                f"{len(want)} window blocks needed, "
                f"{len(self._free) + self._evictable()} free"
            )
        for table, i in want:
            nb = self._acquire()
            self._ref[nb] = 1
            table[i] = nb
            self._window_live.add(nb)
        return released

    def _release_behind(self, seq_id: str, first_query: int) -> int:
        """Give back every window-group block no query at or after
        `first_query` can see."""
        tables, held_lo = self._tables[seq_id], self._held_lo[seq_id]
        released = 0
        for g, w in enumerate(self.group_windows):
            if not w:
                continue
            table = tables[g]
            lo, _ = self._span(w, first_query, first_query)
            for i in range(held_lo[g], min(lo, len(table))):
                if table[i] != self.NULL_BLOCK:
                    self._release_one(table[i])
                    table[i] = self.NULL_BLOCK
                    released += 1
            held_lo[g] = max(held_lo[g], lo)
        self.window_released += released
        return released

    def register_computed(
        self,
        seq_id: str,
        token_ids: Sequence[int],
        num_computed: int,
    ) -> None:
        """Register every newly-FULL block whose KV is written (positions
        < `num_computed`) in the prefix index. Must only be called after the
        engine has actually landed those positions' K/V — registering ahead
        of the compute would serve garbage to the next prompt.

        If a block's key already has a canonical twin (same content computed
        by an earlier sequence), this table adopts the twin and releases its
        own copy — identical prefixes converge to identical tables.

        With window groups, positions below `num_computed` being landed
        means the next query sits at or after it: once the full blocks are
        registered, the blocks behind its window are released, so that at
        rest a sequence holds the window and at most one block more."""
        landed = min(num_computed, len(token_ids))
        if landed > self._landed.get(seq_id, 0):
            self._landed[seq_id] = landed
        self._register_full_blocks(seq_id, token_ids, num_computed)
        if self._sliding:
            self._release_behind(seq_id, landed)

    def _register_full_blocks(self, seq_id, token_ids, num_computed) -> None:
        if not self.caching:
            return
        chain = self._chain.setdefault(seq_id, [])
        tables = self._tables[seq_id]
        full = min(num_computed, len(token_ids)) // self.block_size
        while len(chain) < full:
            i = len(chain)
            mine = tuple(t[i] for t in tables)
            if self.NULL_BLOCK in mine:
                # A group no longer holds this block (it slid past before
                # the block could be registered): the chain ends here.
                break
            prev = chain[-1] if chain else b""
            h = _chain_hash(
                prev, token_ids[i * self.block_size:(i + 1) * self.block_size]
            )
            canon = self._index.get(h)
            if canon is not None and canon != mine:
                for w, table, b, c in zip(self.group_windows, tables, mine, canon):
                    if b != c:
                        self._incref(c)
                        table[i] = c
                        if w:
                            self._window_live.add(c)
                        self._release_one(b)
            elif canon is None:
                self._index[h] = mine
                for b in mine:
                    self._hash_of[b] = h
            self._touch_hot(h)
            chain.append(h)

    # ----------------------------------------------------- tier / transfer
    def holds(self, h: bytes) -> Optional[int]:
        """Physical block registered under content hash `h` (the first
        group's), or None."""
        blocks = self._index.get(h)
        return None if blocks is None else blocks[0]

    def adopt_block(self, h: bytes, blob) -> Optional[int]:
        """Adopt externally-computed KV content (a remote replica's export,
        fetched by `engine.import_blocks`): acquire a block, register it
        under `h`, park it on the cached LRU (MRU end), and queue the bytes
        as a pending LOAD the engine lands before its next kernel. Returns
        the block, or None when the pool has nothing to give (the import
        degrades to recompute — never an error)."""
        if self._G > 1:
            raise NotImplementedError("adopt_block over several KV groups")
        if not self.caching or h in self._index:
            return None
        try:
            b = self._acquire()
        except KVCacheExhausted:
            return None
        self._index[h] = (b,)
        self._hash_of[b] = h
        self._cached[b] = None  # ref 0, content retained, MRU end
        self._pending_loads[b] = (h, blob, True)
        self._touch_hot(h)
        return b

    def export_sources(self, digests: Sequence[bytes]) -> List[Optional[Tuple]]:
        """Where each digest's bytes live right now, aligned with `digests`:
        ("hbm", block) for registered blocks whose content is landed,
        ("blob", bytes) for content still in flight (pending load) or only
        host-tier-resident, None when nowhere. The engine reads HBM sources
        at a step boundary, where the arrays are stable."""
        if self._G > 1:
            raise NotImplementedError("export_sources over several KV groups")
        out: List[Optional[Tuple]] = []
        for h in digests:
            b = self.holds(h)
            if b is not None:
                pending = self._pending_loads.get(b)
                if pending is not None and pending[0] == h:
                    out.append(("blob", pending[1]))
                else:
                    out.append(("hbm", b))
            elif self._tier is not None:
                blob = self._tier.peek(h)
                out.append(None if blob is None else ("blob", blob))
            else:
                out.append(None)
        return out

    def drain_loads(self) -> List[Tuple[bytes, int, object, bool]]:
        """(hash, block, bytes, remote) loads the engine must land in the
        HBM arrays before its next kernel launch — host-tier hits at
        admission (remote=False) + adopted imports (remote=True). Entries
        for since-evicted blocks were already dropped at eviction."""
        out = [
            (h, b, blob, remote)
            for b, (h, blob, remote) in self._pending_loads.items()
        ]
        self._pending_loads.clear()
        return out

    def drain_saves(self) -> List[Tuple[bytes, int]]:
        """(hash, block) eviction saves: the engine must copy these blocks'
        HBM bytes into the host tier BEFORE applying COW copies, loads, or
        kernels (the block is already reallocated — this drain order is
        what keeps the bytes readable)."""
        out, self._pending_saves = self._pending_saves, []
        return out

    def drain_cow(self) -> List[Tuple[int, int]]:
        """(src, dst) physical block copies queued by copy-on-write forks.
        The engine MUST apply these to the KV arrays before its next kernel
        launch; draining also re-exposes the sources to eviction."""
        out, self._pending_copies = self._pending_copies, []
        return out

    def free(self, seq_id: str) -> int:
        """Release a finished/preempted sequence's references. Blocks
        reaching refcount 0 return to the free list — except registered
        (full, hashed) blocks, which are RETAINED on the cached LRU list to
        serve future prefix hits until evicted. Raises KeyError on an
        unknown (or already-freed) seq_id — the double-free guard."""
        tables = self._tables.pop(seq_id)  # KeyError = double free
        del self._lens[seq_id]
        del self._held_lo[seq_id]
        self._chain.pop(seq_id, None)
        self._landed.pop(seq_id, None)
        held = [b for t in tables for b in t if b != self.NULL_BLOCK]
        for b in held:
            self._release_one(b)
        if self.state_slots:
            self._free_states.append(self._state_of.pop(seq_id))
            self.states_released += 1
        return len(held)

    def check_invariants(self) -> None:
        """Every block is in exactly one place (free xor cached xor live),
        refcounts match table references, and the hash index is bijective
        over registered blocks."""
        seen = set(self._free)
        assert len(seen) == len(self._free), "free list has duplicates"
        assert self.NULL_BLOCK not in seen, "null block on the free list"
        for b in self._cached:
            assert b not in seen, f"block {b} free AND cached"
            assert b in self._hash_of, f"cached block {b} has no registered hash"
            assert b not in self._ref, f"cached block {b} has live refs"
            seen.add(b)
        refs: Dict[int, int] = {}
        for sid, tables in self._tables.items():
            assert len(tables) == self._G, f"{sid!r}: {len(tables)} tables"
            for w, table in zip(self.group_windows, tables):
                assert len(table) == self.blocks_for(self._lens[sid]), (
                    f"{sid!r}: table/len mismatch"
                )
                assert len(self._chain.get(sid, ())) <= len(table), (
                    f"{sid!r}: more registered blocks than table entries"
                )
                held = [i for i, b in enumerate(table) if b != self.NULL_BLOCK]
                assert w or len(held) == len(table), (
                    f"{sid!r}: a global group lost a block"
                )
                assert not held or held == list(range(held[0], held[-1] + 1)), (
                    f"{sid!r}: a window group's blocks are not one run"
                )
                for i in held:
                    b = table[i]
                    assert b not in self._free and b not in self._cached, (
                        f"block {b} live AND free/cached"
                    )
                    refs[b] = refs.get(b, 0) + 1
        assert refs == self._ref, (
            f"refcount drift: counted {refs}, recorded {self._ref}"
        )
        in_window = {b for tables in self._tables.values()
                     for w, table in zip(self.group_windows, tables) if w
                     for b in table if b != self.NULL_BLOCK}
        assert in_window == self._window_live, (
            f"window blocks drift: counted {in_window}, recorded {self._window_live}"
        )
        seen.update(refs)
        assert len(seen) == self.num_blocks - 1, "lost/leaked blocks"
        for h, blocks in self._index.items():
            assert len(blocks) == self._G, f"index entry of {len(blocks)} blocks"
            for b in blocks:
                assert self._hash_of.get(b) == h, f"index/hash_of drift on block {b}"
        for b, h in self._hash_of.items():
            assert b in self._index.get(h, ()), f"hash_of/index drift on block {b}"
        for sid, landed in self._landed.items():
            assert landed <= self._lens[sid], (
                f"{sid!r}: landed watermark {landed} past allocation "
                f"{self._lens[sid]}"
            )
        held = sorted(self._state_of.values())
        assert len(set(held)) == len(held), "a state slot held twice"
        assert sorted(held + self._free_states) == list(range(1, self.state_slots + 1)), (
            "lost/leaked state slots")
        assert set(self._state_of) == (set(self._tables) if self.state_slots else set()), (
            "state slots and block tables name different sequences")
        for b, (h, *_rest) in self._pending_loads.items():
            assert b not in self._free, f"pending-load block {b} on free list"
            assert self._hash_of.get(b) == h, (
                f"pending-load block {b} no longer registered under its hash"
            )
