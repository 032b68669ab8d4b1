"""Block-table KV manager: length-proportional cache allocation.

The serving mirror of Synergy's memory-sensitivity argument (PAPER.md §4):
`CachePool` gives every request a full ``max_len`` cache row — the
GPU-proportional over-allocation the paper argues against. ``BlockManager``
instead carves one head-major ``[n_blocks, Hkv, block_size, D]`` buffer per
layer's K and V into fixed-size blocks: a request at length L holds exactly
``ceil(L / block_size)`` blocks behind a per-request block table, so a
40-token prompt in a 256-position pool costs 3 blocks of 16 instead of a
256-row.

Admission is watermark-based: a request is admitted when its *prompt* blocks
fit while keeping ``watermark * n_blocks`` blocks free as decode-growth
headroom. Growth (``ensure``) may eat into the reserve; when the pool is
truly out of blocks the engine preempts the most recently admitted request
(its blocks are freed and its tokens regenerated identically after
re-admission — prefill is deterministic).

Blocks and decode slots are both recycled FIFO, mirroring ``CachePool``'s
recycling discipline, and a freed request's table row is cleared to -1 so a
re-issued block can never be read through a stale table.

Prefix caching (``prefix_cache=True``) adds a content-addressed layer on
top: every FULL prompt block is identified by a rolling hash of its tokens
chained to its predecessor's hash, so "same hash" implies "same prompt
prefix" and therefore — prefill being deterministic — identical K/V
content. A request whose leading hashes are already cached *shares* those
blocks (ref-counted) and skips both their allocation and their prefill
compute; only the unshared suffix is computed. The partial tail block is
always privately allocated (copy-on-write discipline: shared blocks are
never written after their owner's prefill, appends land in fresh blocks),
so a tenant's decode can never corrupt a neighbour's prefix. Blocks whose
refcount drops to zero stay cached in an *evictable* FIFO and are only
reclaimed when the free list runs dry — a re-arriving prefix revives them
for free. For MoE, the per-layer expert-assignment counts after each block
are snapshotted alongside the hash (and the routing capacity is folded into
the hash seed), so a prefix-hit resume routes token-for-token like a cold
prefill.
"""
from __future__ import annotations

import hashlib
import math
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.obs import NULL_TRACER


@dataclass
class _PrefixEntry:
    """One cached full prompt block: hash -> (block id, refcount, state).

    ``ready`` flips when the owning request's prefill has actually written
    the block's K/V (``commit_block``); a hit on an unready entry defers the
    hitting request instead of reading half-written content. ``state`` is
    the family's cross-chunk prefill carry *after* this block (MoE expert
    counts; None for dense/vlm). ``retired`` marks an entry force-flushed
    (``flush_prefix``) while still referenced: it stays for refcounting but
    is unhittable, and its block is released when the last holder frees —
    deleting it outright would double-free the block (every sharer's
    ``free`` would see a private block and return it to the free list).
    """
    block: int
    refs: int = 0
    ready: bool = False
    state: object = field(default=None, repr=False)
    retired: bool = False


class BlockManager:
    """Paged decode cache over a model's ``init_paged_cache`` pytree.

    Exposes the pool surface ``ContinuousScheduler`` drives — ``alloc_for`` /
    ``free`` / ``max_len`` / ``validate_request`` — plus the block-granular
    calls the paged engine uses per step (``ensure``, ``table_rows``,
    ``report``) and the prefix-cache surface (``cached_tokens``,
    ``resume_state``, ``commit_block``).
    """

    def __init__(self, model, n_slots: int, max_len: int,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 watermark: float = 0.05, dtype=None,
                 prefix_cache: bool = False, tracer=NULL_TRACER):
        if model.init_paged_cache is None:
            raise ValueError(
                f"family {model.cfg.family!r} has no paged decode cache "
                "(recurrent state is O(1); use the contiguous CachePool)")
        self.model = model
        #: obs.Tracer for block-pool events (alloc / grow / free /
        #: prefix_evict); the engine's clock is inherited via ``tracer.step``.
        #: The falsy NULL_TRACER default keeps every emission one branch.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.n_slots = n_slots
        self.max_len = max_len
        self.block_size = block_size
        self.max_blocks = -(-max_len // block_size)   # table width per slot
        #: default pool capacity == the contiguous pool's token capacity
        self.n_blocks = (n_blocks if n_blocks is not None
                         else n_slots * self.max_blocks)
        self.watermark = float(watermark)   # fraction; re-applied on shrink
        self.watermark_blocks = math.ceil(watermark * self.n_blocks)
        #: fault injection (chaos.FaultInjector pool_shrink): blocks revoked
        #: from the pool mid-run, a deficit still owed from in-use blocks,
        #: and the buffer capacity audits reconcile against.
        self._revoked: List[int] = []
        self._revoke_deficit = 0
        self._total_blocks = self.n_blocks
        #: per-tenant watermark headroom (tenant.TenantAllocation.reserves):
        #: when set, a tenant admitting must keep only the OTHER tenants'
        #: reserve free — its own headroom is admission-spendable, so
        #: insensitive tenants' headroom is effectively stolen by the
        #: sensitive ones the allocator favoured. Empty dict = the flat
        #: single-watermark rule.
        self.tenant_reserves: Dict[str, int] = {}
        self._dtype = dtype            # kept for grow_physical reallocation
        self._block_axes = None        # leaf block-axis map, probed lazily
        self.buffers = model.init_paged_cache(self.n_blocks, block_size,
                                              dtype)
        self._free_blocks = deque(range(self.n_blocks))
        self._free_slots = deque(range(n_slots))
        self._in_use: set = set()
        self.tables = np.full((n_slots, self.max_blocks), -1, np.int32)
        self._lengths = np.zeros((n_slots,), np.int64)  # tokens owned
        # -- prefix cache ----------------------------------------------------
        self.prefix_cache = prefix_cache
        self._entries: Dict[int, _PrefixEntry] = {}       # hash -> entry
        self._evictable: "OrderedDict[int, None]" = OrderedDict()  # FIFO
        #: per-slot chain of (hash | None, owned) for the prompt's full
        #: blocks; None marks a private block (hash already owned elsewhere)
        self._chains: Dict[int, List[Tuple[Optional[int], bool]]] = {}
        self._cached_tokens = np.zeros((n_slots,), np.int64)
        self._resume: Dict[int, object] = {}
        self.prefix_blocks_total = 0   # full+partial prompt blocks allocated
        self.prefix_blocks_hit = 0     # of those, served from the cache
        #: True iff the LAST alloc_for returned None because a donor was
        #: still prefilling (vs pool exhaustion) — the scheduler admits
        #: unrelated requests past a deferral but stops on exhaustion.
        self.deferred_last_alloc = False
        #: slots whose table row changed since the last ``drain_dirty`` —
        #: the engine mirrors the block tables on device between decode
        #: horizons and only re-uploads the dirty rows (delta updates at
        #: admission / growth / free instead of per-step re-upload).
        self._dirty_slots: set = set()

    # -- block math ----------------------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 0) // self.block_size)

    @property
    def n_free(self) -> int:
        return len(self._free_slots)

    @property
    def free_blocks(self) -> int:
        """Blocks available to allocation: truly free + evictable cached."""
        return len(self._free_blocks) + len(self._evictable)

    @property
    def evictable_blocks(self) -> int:
        return len(self._evictable)

    @property
    def in_use(self):
        return frozenset(self._in_use)

    # -- prefix hashing ------------------------------------------------------
    def _hash_chain(self, prompt: np.ndarray) -> List[int]:
        """Rolling content hashes of the prompt's FULL blocks. The seed folds
        in the routing capacity for MoE (two prompts sharing tokens but not
        capacity must not share blocks — capacity drops would differ)."""
        salt = 0
        if self.model.cfg.family == "moe":
            from repro.models.moe import capacity
            salt = capacity(self.model.cfg, len(prompt))
        prev = salt.to_bytes(8, "little", signed=True)
        hashes = []
        for i0 in range(0, (len(prompt) // self.block_size) * self.block_size,
                        self.block_size):
            h = hashlib.blake2b(
                prev + np.ascontiguousarray(
                    prompt[i0:i0 + self.block_size], np.int64).tobytes(),
                digest_size=16).digest()
            hashes.append(int.from_bytes(h, "little"))
            prev = h
        return hashes

    def _take_block(self) -> int:
        """A free block, evicting the oldest refcount-0 cached block if the
        free list is dry (its hash entry is dropped: content unreachable)."""
        if self._free_blocks:
            return self._free_blocks.popleft()
        h, _ = self._evictable.popitem(last=False)
        if self.tracer:
            self.tracer.emit("prefix_evict", blocks=1)
        return self._entries.pop(h).block

    def _release_block(self, blk: int) -> None:
        """Return a block to the pool — or to a pending revocation: after a
        ``shrink`` that could not find enough idle blocks, the deficit is
        collected here as in-use blocks come back."""
        if self._revoke_deficit > 0:
            self._revoke_deficit -= 1
            self._revoked.append(blk)
        else:
            self._free_blocks.append(blk)

    # -- admission -----------------------------------------------------------
    def validate_request(self, req) -> None:
        """Reject requests that can never run on this pool."""
        need = len(req.prompt) + req.max_new_tokens
        if need > self.max_len:
            raise ValueError(
                f"request needs {need} cache positions but the pool's block "
                f"tables span {self.max_len}")
        if self.blocks_for(need) > self.n_blocks:
            raise ValueError(
                f"request needs {self.blocks_for(need)} blocks but the pool "
                f"holds {self.n_blocks}")
        if self.blocks_for(len(req.prompt)) + self.watermark_blocks \
                > self.n_blocks:
            raise ValueError(
                f"prompt needs {self.blocks_for(len(req.prompt))} blocks "
                f"which can never clear the {self.watermark_blocks}-block "
                f"admission watermark on a {self.n_blocks}-block pool")

    def _blocks_clear_watermark(self, n_new_blocks: int,
                                tenant: Optional[str] = None) -> bool:
        """The watermark rule: ``n_new_blocks`` fresh blocks fit while the
        reserve stays free (``can_admit`` and ``alloc_for`` must agree —
        alloc_for charges only the non-cached blocks). With per-tenant
        reserves installed, a known tenant only keeps the OTHER tenants'
        headroom free — its own share of the reserve is spendable at its
        admission."""
        reserve = self.watermark_blocks
        if tenant is not None and tenant in self.tenant_reserves:
            reserve = min(reserve,
                          sum(self.tenant_reserves.values())
                          - self.tenant_reserves[tenant])
        return self.free_blocks - n_new_blocks >= reserve

    def can_admit(self, n_tokens: int) -> bool:
        """Watermark admission: prompt blocks fit AND the high-watermark
        reserve stays free for decode growth of already-admitted tenants.
        (Cache-blind: a prompt with cached prefix blocks may be admissible
        even when this returns False — ``alloc_for`` is the authority.)"""
        return (bool(self._free_slots)
                and self._blocks_clear_watermark(self.blocks_for(n_tokens)))

    def alloc_for(self, req) -> Optional[int]:
        """Admit ``req``: claim a slot + its prompt's blocks; None if the
        watermark would be violated (the scheduler keeps it queued).

        With the prefix cache on, the prompt's leading full blocks are
        looked up by content hash: ready hits are *shared* (refcount++, no
        new block, no prefill compute — ``cached_tokens`` tells the engine
        where to resume); a hit on a block another tenant is still
        prefilling returns None, deferring the request one round so it can
        share the finished block instead of racing the writer. The last
        chunk is never served from cache — its logits seed the first
        generated token."""
        n = len(req.prompt)
        need = self.blocks_for(n)
        hashes: List[int] = []
        hits = revived = 0
        self.deferred_last_alloc = False
        if self.prefix_cache:
            # the chain is pure content: memoize it on the (immutable-prompt)
            # request so per-step admission retries do not rehash.
            memo_key = (self.block_size, self.model.cfg.arch_id)
            memo = getattr(req, "_prefix_hashes", None)
            if memo is not None and memo[0] == memo_key:
                hashes = memo[1]
            else:
                hashes = self._hash_chain(np.asarray(req.prompt))
                req._prefix_hashes = (memo_key, hashes)
            hit_cap = (n - 1) // self.block_size
            for idx, h in enumerate(hashes[:hit_cap]):
                e = self._entries.get(h)
                if e is None or e.retired:   # retired = flushed, unhittable
                    break
                if not e.ready:
                    # donor mid-prefill: join next round (the scheduler may
                    # still admit unrelated requests this round)
                    self.deferred_last_alloc = True
                    return None
                hits += 1
                # a refcount-0 hit revives a block ``free_blocks`` counts
                # as available: it costs no NEW block but still shrinks
                # availability, so charge it or the private-suffix take
                # below can run the pool dry mid-allocation.
                revived += e.refs == 0
        if (not self._free_slots
                or not self._blocks_clear_watermark(
                    need - hits + revived, getattr(req, "tenant", None))):
            return None
        slot = self._free_slots.popleft()
        self._in_use.add(slot)
        chain: List[Tuple[Optional[int], bool]] = []
        for j in range(need):
            if j < hits:
                e = self._entries[hashes[j]]
                if e.refs == 0:
                    self._evictable.pop(hashes[j], None)
                e.refs += 1
                self.tables[slot, j] = e.block
                chain.append((hashes[j], False))
            else:
                self.tables[slot, j] = self._take_block()
                if self.prefix_cache and j < len(hashes):
                    if hashes[j] in self._entries:
                        chain.append((None, False))   # hash owned elsewhere
                    else:
                        self._entries[hashes[j]] = _PrefixEntry(
                            block=int(self.tables[slot, j]), refs=1)
                        chain.append((hashes[j], True))
        self._lengths[slot] = n
        self._dirty_slots.add(slot)
        if self.prefix_cache:
            self._chains[slot] = chain
            self._cached_tokens[slot] = hits * self.block_size
            self._resume[slot] = (self._entries[hashes[hits - 1]].state
                                  if hits else None)
            self.prefix_blocks_total += need
            self.prefix_blocks_hit += hits
        if self.tracer:
            self.tracer.emit("block_alloc", slot=slot, blocks=need - hits,
                             hits=hits)
        return slot

    # -- prefix-cache surface (engine prefill hooks) --------------------------
    def cached_tokens(self, slot: int) -> int:
        """Prompt positions already covered by cache hits: prefill resumes
        here (0 when the prefix cache is off or missed)."""
        return int(self._cached_tokens[slot])

    def resume_state(self, slot: int):
        """The cross-chunk prefill carry snapshotted after the last hit
        block (MoE expert counts), or None for a cold start."""
        return self._resume.get(slot)

    def commit_block(self, slot: int, block_idx: int, state=None) -> None:
        """Mark a prompt block's content written (the engine calls this as
        its prefill finishes each full block): the entry becomes hittable
        and carries the prefill state snapshot for MoE-exact resumes."""
        chain = self._chains.get(slot, ())
        if block_idx >= len(chain):
            return
        h, owned = chain[block_idx]
        if not owned or h is None:
            return
        e = self._entries.get(h)
        if e is not None and e.block == int(self.tables[slot, block_idx]):
            e.ready = True
            e.state = state

    def ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow ``slot`` to cover ``n_tokens`` positions (decode append).
        May eat into the watermark reserve; False when the pool is dry.
        Growth blocks are always private — appends never touch a shared
        prefix block (the copy-on-write discipline)."""
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        have = have0 = int((self.tables[slot] >= 0).sum())
        while have * self.block_size < n_tokens:
            if not self._free_blocks and not self._evictable:
                return False
            self.tables[slot, have] = self._take_block()
            self._dirty_slots.add(slot)
            have += 1
        if self.tracer and have > have0:
            self.tracer.emit("block_grow", slot=slot, blocks=have - have0)
        self._lengths[slot] = max(self._lengths[slot], n_tokens)
        return True

    def owned_blocks(self, slot: int) -> int:
        """Blocks currently assigned to ``slot``'s table."""
        return int((self.tables[slot] >= 0).sum())

    def drain_dirty(self) -> set:
        """Slots whose table rows changed since the last drain (clears the
        set) — the engine's device-resident table mirror syncs these rows."""
        dirty, self._dirty_slots = self._dirty_slots, set()
        return dirty

    def free(self, slot: int) -> None:
        """Release a request's slot and blocks (FIFO recycle, stale table
        entries cleared so re-issued blocks are unreachable). Shared prefix
        blocks are only de-referenced: at refcount 0 they park in the
        evictable FIFO — still hittable — until the free list runs dry."""
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        self._in_use.remove(slot)
        chain = self._chains.pop(slot, ())
        n_freed = n_shared = 0
        for j in range(self.max_blocks):
            blk = int(self.tables[slot, j])
            if blk < 0:
                continue
            n_freed += 1
            h = chain[j][0] if j < len(chain) else None
            e = self._entries.get(h) if h is not None else None
            if e is not None and e.block == blk:
                n_shared += 1
                e.refs -= 1
                if e.refs == 0:
                    if e.ready and not e.retired:
                        self._evictable[h] = None
                    else:   # owner bailed before writing, or force-flushed
                        # at nonzero refcount: unservable either way
                        del self._entries[h]
                        self._release_block(blk)
            else:
                self._release_block(blk)
        self.tables[slot] = -1
        self._dirty_slots.add(slot)
        self._lengths[slot] = 0
        self._cached_tokens[slot] = 0
        self._resume.pop(slot, None)
        self._free_slots.append(slot)
        if self.tracer:
            self.tracer.emit("block_free", slot=slot, blocks=n_freed,
                             shared=n_shared)

    # -- fault injection (chaos.FaultInjector recovery surface) --------------
    def shrink(self, n: int) -> int:
        """Revoke up to ``n`` blocks of capacity mid-run (a ``pool_shrink``
        fault: a co-tenant claims the memory). Idle blocks go first — the
        free list, then evictable cached blocks (their entries dropped) —
        and any remainder becomes a *deficit* collected as in-use blocks
        free (``_release_block``). Capacity accounting (``n_blocks``, the
        watermark) rescales immediately, so admission decisions see the
        shrunken pool at once; per-tenant reserves are the engine's to
        rescale (``TenantAllocation.rescaled_reserves``). At least one
        block of capacity always survives. Returns the blocks revoked."""
        take = max(0, min(int(n), self.n_blocks - 1))
        got = 0
        while got < take and (self._free_blocks or self._evictable):
            self._revoked.append(self._take_block())
            got += 1
        self._revoke_deficit += take - got
        self.n_blocks -= take
        self.watermark_blocks = math.ceil(self.watermark * self.n_blocks)
        return take

    def expand(self, n: int) -> int:
        """Return up to ``n`` previously revoked blocks (``pool_restore``).
        Deficit cancels first — those blocks never actually left the
        tables — then physically revoked blocks rejoin the free list."""
        give = min(int(n), len(self._revoked) + self._revoke_deficit)
        cancel = min(give, self._revoke_deficit)
        self._revoke_deficit -= cancel
        for _ in range(give - cancel):
            self._free_blocks.append(self._revoked.pop())
        self.n_blocks += give
        self.watermark_blocks = math.ceil(self.watermark * self.n_blocks)
        return give

    def grow_physical(self, n: int, sharding=None) -> int:
        """Grow TRUE capacity past the construction-time allocation (a
        ``device_join`` bringing more memory than any failure revoked):
        allocate larger cache buffers and migrate every existing block's
        content into them along each leaf's block axis — a pure state move,
        never a recompute, so in-flight decodes resume token-identically.
        ``sharding`` (the plan's ``cache_sharding`` pytree) re-places the
        migrated buffers on the mesh; the block axis is unsharded in the
        paged specs, so the same NamedShardings apply at any capacity.

        Block ids are stable — the new blocks take ids past the old
        capacity and join the free list — so live tables, prefix-cache
        entries and the revocation ledger all survive untouched. Returns
        the blocks added (0 for ``n <= 0``)."""
        import jax

        n = int(n)
        if n <= 0:
            return 0
        if self._block_axes is None:
            from repro.serve.cache import _batch_axis
            probe_a = jax.eval_shape(
                lambda: self.model.init_paged_cache(3, self.block_size,
                                                    self._dtype))
            probe_b = jax.eval_shape(
                lambda: self.model.init_paged_cache(5, self.block_size,
                                                    self._dtype))
            self._block_axes = jax.tree_util.tree_map(_batch_axis, probe_a,
                                                      probe_b)
        old_total = self._total_blocks
        new_buffers = self.model.init_paged_cache(old_total + n,
                                                  self.block_size,
                                                  self._dtype)

        def migrate(new, old, ax):
            sel = (slice(None),) * ax + (slice(0, old.shape[ax]),)
            return new.at[sel].set(old)

        new_buffers = jax.tree_util.tree_map(migrate, new_buffers,
                                             self.buffers, self._block_axes)
        if sharding is not None:
            new_buffers = jax.device_put(new_buffers, sharding)
        self.buffers = new_buffers
        self._free_blocks.extend(range(old_total, old_total + n))
        self._total_blocks = old_total + n
        self.n_blocks += n
        self.watermark_blocks = math.ceil(self.watermark * self.n_blocks)
        return n

    def flush_prefix(self) -> int:
        """Force-evict the prefix cache (a ``prefix_flush`` fault).
        Refcount-0 entries release their blocks immediately; entries still
        referenced by live requests are *retired* — unhittable for future
        admissions, their blocks released when the last holder frees.
        Returns entries flushed (freed + retired)."""
        freed = 0
        for h in list(self._evictable):
            del self._evictable[h]
            self._release_block(self._entries.pop(h).block)
            freed += 1
        retired = 0
        for e in self._entries.values():
            if not e.retired:
                e.retired = True
                retired += 1
        if freed and self.tracer:
            self.tracer.emit("prefix_evict", blocks=freed)
        return freed + retired

    def audit(self) -> Dict[str, int]:
        """Block-conservation check: every block the pool was built with is
        in exactly ONE of {free list, revoked, a table (counted once across
        sharers), evictable cache}, modulo the outstanding revocation
        deficit (those blocks sit in tables, owed). Also checks refcount
        agreement (an entry's refs equals its block's table multiplicity)
        and slot/table consistency. Raises RuntimeError on any violation —
        the engine asserts this after every injected fault — and returns a
        summary dict when clean."""
        problems: List[str] = []
        free = list(self._free_blocks)
        free_set = set(free)
        if len(free_set) != len(free):
            problems.append(f"duplicate blocks in the free list: {free}")
        revoked_set = set(self._revoked)
        if len(revoked_set) != len(self._revoked):
            problems.append(f"duplicate revoked blocks: {self._revoked}")
        if free_set & revoked_set:
            problems.append(f"free∩revoked: {sorted(free_set & revoked_set)}")
        # table multiplicity per block; idle slots must have empty tables
        table_refs: Dict[int, int] = {}
        for slot in range(self.n_slots):
            row = self.tables[slot]
            if slot not in self._in_use:
                if (row >= 0).any():
                    problems.append(f"idle slot {slot} holds table blocks")
                continue
            for blk in row[row >= 0]:
                table_refs[int(blk)] = table_refs.get(int(blk), 0) + 1
        table_set = set(table_refs)
        for name, other in (("free", free_set), ("revoked", revoked_set)):
            if table_set & other:
                problems.append(
                    f"table∩{name}: {sorted(table_set & other)}")
        # entry <-> table refcount agreement
        entry_blocks: Dict[int, int] = {}
        for h, e in self._entries.items():
            if e.block in entry_blocks:
                problems.append(f"two entries share block {e.block}")
            entry_blocks[e.block] = e.refs
            if e.refs != table_refs.get(e.block, 0):
                problems.append(
                    f"entry {h:#x} refs={e.refs} but block {e.block} has "
                    f"table multiplicity {table_refs.get(e.block, 0)}")
            if e.refs == 0 and h not in self._evictable:
                problems.append(
                    f"refcount-0 entry {h:#x} not in the evictable FIFO")
        for blk, cnt in table_refs.items():
            if cnt > 1 and blk not in entry_blocks:
                problems.append(
                    f"block {blk} shared by {cnt} tables without an entry")
        evict_blocks = {self._entries[h].block for h in self._evictable
                        if h in self._entries}
        missing = set(self._evictable) - set(self._entries)
        if missing:
            problems.append(f"evictable hashes without entries: "
                            f"{[hex(h) for h in missing]}")
        # the conservation sum: deficit blocks live in tables, still owed
        accounted = (len(free_set) + len(revoked_set) + len(table_set)
                     + len(evict_blocks - table_set))
        if accounted != self._total_blocks:
            problems.append(
                f"{accounted} blocks accounted for "
                f"(free={len(free_set)} revoked={len(revoked_set)} "
                f"table={len(table_set)} evictable={len(evict_blocks)}) "
                f"of {self._total_blocks}")
        if (self.n_blocks + len(self._revoked) + self._revoke_deficit
                != self._total_blocks):
            problems.append(
                f"capacity arithmetic broken: n_blocks={self.n_blocks} "
                f"+ revoked={len(self._revoked)} "
                f"+ deficit={self._revoke_deficit} != {self._total_blocks}")
        if problems:
            raise RuntimeError("block audit failed:\n  "
                               + "\n  ".join(problems))
        return {"free": len(free_set), "revoked": len(revoked_set),
                "deficit": self._revoke_deficit, "in_table": len(table_set),
                "evictable": len(evict_blocks),
                "capacity": self.n_blocks}

    # -- decode-step views ---------------------------------------------------
    def table_rows(self, slots) -> np.ndarray:
        """[len(slots), max_blocks] int32 block tables for a decode batch."""
        return self.tables[np.asarray(slots, np.int64)]

    # -- occupancy / fragmentation -------------------------------------------
    def report(self) -> Dict[str, float]:
        """Occupancy + fragmentation snapshot (CLI summary / tests). Shared
        blocks count once toward ``used_blocks`` but every tenant's tokens
        count toward ``used_tokens``, so fragmentation is clamped at 0."""
        used_blocks = self.n_blocks - self.free_blocks
        allocated = used_blocks * self.block_size
        used_tokens = int(self._lengths.sum())
        return {
            "n_blocks": self.n_blocks,
            "block_size": self.block_size,
            "used_blocks": used_blocks,
            "free_blocks": self.free_blocks,
            "evictable_blocks": self.evictable_blocks,
            "watermark_blocks": self.watermark_blocks,
            "occupancy": used_blocks / self.n_blocks if self.n_blocks else 0.0,
            "used_tokens": used_tokens,
            "allocated_tokens": allocated,
            # internal fragmentation: allocated-but-unused tail positions of
            # each tenant's last block.
            "internal_fragmentation": max(
                0.0, 1.0 - used_tokens / allocated) if allocated else 0.0,
            "prefix_blocks_total": self.prefix_blocks_total,
            "prefix_blocks_hit": self.prefix_blocks_hit,
            "revoked_blocks": len(self._revoked),
            "revoke_deficit": self._revoke_deficit,
        }
