"""Paged KV cache — host-side block accounting over the device block pool.

The PagedAttention idea (vLLM) recast for the XLA serving stack: the device
holds ONE physical block pool ``{"k","v": [L, num_blocks, Hk, block_size,
D]}`` (:func:`paddle_tpu.models.generation.init_paged_pool`); a sequence
owns an ordered list of physical blocks recorded in its slot's row of the
block-table matrix, and the compiled decode step gathers exactly those
blocks. This module is the HOST half: a ref-counted block manager with a
content-hash prefix cache plus the ``[max_slots, W]`` block-table matrix
the engine ships with every dispatch. No jax import here — device math
lives in ``models/generation.py`` (the block copy helpers lazily import
jax only to pass block indices as DEVICE scalars, keeping one compiled
slice/update program across all block indices).

Allocation policy (ISSUE 5): **on-demand** — a sequence holds only the
blocks covering KV entries it has actually filled (admission maps/allocates
the prompt; decode extends block by block as ``seq_len`` grows). When the
pool runs dry mid-decode the ENGINE preempts the newest-admitted running
sequence (``scheduler.Scheduler.preempt``) instead of refusing progress.
The legacy reservation-at-admission policy (``prompt + max_new - 1``
entries reserved up front, no preemption needed) survives behind
``preempt=False`` / ``FLAGS_serving_preempt=0`` as a conservative
fallback, tested end-to-end. Physical block 0 is the NULL block — the
masked-lane scatter target — and is never allocated.

Prefix cache: every FULL block's token ids are content-hashed into a
CHAINED key (the key covers the whole block-aligned prefix, not just the
block — two different prefixes sharing one identical middle block must not
collide), so admissions sharing a system-prompt/few-shot prefix map the
cached blocks by refcount instead of re-running prefill over them. Blocks
whose refcount drops to 0 stay cached on an LRU list and are evicted only
when the free list runs dry.

Host offload tier (ISSUE 16): with a :class:`~paddle_tpu.inference.
serving.offload.HostOffloadTier` attached, an LRU-evicted registered
block swaps OUT to the bounded host pool instead of dying (both eviction
sites — ``alloc``'s LRU branch and ``register``'s tenant-quota recycle),
and ``admit``'s chain walk consults the tier on a device miss: a
verified host hit allocates a device block, H2D-restores the bytes, and
re-registers the key — zero recompute. A key is device-resident XOR
host-resident: registering a key on device discards any stale host copy,
and a successful host take moves the entry back to device.
"""

from __future__ import annotations

import math
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["BlockManager", "PagedKVCache", "prefix_block_chain"]


def prefix_block_chain(ids: Sequence[int], block_size: int, upto: int,
                       start: int = 0, prev_key: Optional[int] = None,
                       base: int = 0, namespace: Optional[str] = None):
    """Yield ``(key, tokens)`` for the FULL blocks ``start .. upto //
    block_size`` of a sequence — the ONE definition of the chained content
    key (lookup, registration and incremental resumption all walk this,
    so the formula cannot drift between them).

    Key ``i`` hashes (key ``i-1``, the ``block_size`` token ids of block
    ``i``), so equal keys imply equal whole block-aligned prefixes — a
    shared middle block under two different prefixes gets two different
    keys. Keys are still 64-bit hashes, so a hit is VERIFIED against the
    stored block tokens before mapping (:meth:`BlockManager.lookup`);
    ``tokens`` is yielded so registration can store them at zero extra
    cost. ``ids`` is indexed relative to ``base`` (``ids[i * block_size -
    base]`` is block ``i``'s first token), letting callers pass only the
    not-yet-registered tail instead of rebuilding the whole chain.

    ``namespace`` seeds the chain root (ISSUE 19): KV written under a
    LoRA adapter differs from base KV for the same tokens (the k/v
    projections carry the adapter delta), so each adapter hashes in its
    own disjoint key space — a base-cached block can never prefix-hit an
    adapter request or vice versa. ``None`` (base traffic) leaves the
    seed untouched, so every pre-LoRA key — including fleet directory
    entries and host-tier registrations — is bit-identical to before.
    """
    h = prev_key
    if h is None and namespace is not None:
        h = hash(("adapter-ns", namespace))
    for i in range(start, int(upto) // block_size):
        lo = i * block_size - base
        toks = tuple(int(t) for t in ids[lo:lo + block_size])
        h = hash((h, toks))
        yield h, toks


class BlockManager:
    """Ref-counted allocator over the physical block ids ``1..num_blocks-1``
    (block 0 = null) with a content-hash prefix cache.

    Lifecycle of a block: free list -> ``alloc`` (refcount 1) -> optionally
    ``register``\\ ed under its chained content key once its ``block_size``
    KV entries are written -> shared by later sequences via ``lookup`` +
    ``share`` (refcount++) -> ``free`` (refcount--) -> at refcount 0 a
    registered block parks on the EVICTABLE LRU list (still a cache hit!)
    while an unregistered one returns to the free list. ``alloc`` takes
    from the free list first and evicts LRU refcount-0 cached blocks only
    when that runs dry. Double-free and foreign-id frees raise — a serving
    engine that corrupts its accounting serves one sequence's KV to
    another, which must fail loudly.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 tenant_quota: Optional[int] = None):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (1 null + 1 usable), "
                             f"got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # per-tenant prefix-cache quota (ISSUE 6): at most this many
        # blocks registered per tenant key — a tenant flooding unique
        # prompts churns its OWN cache entries instead of LRU-evicting
        # everyone else's system prompt. None = unlimited.
        self.tenant_quota = int(tenant_quota) if tenant_quota else None
        # LIFO free list: hot blocks are reused first (their pool pages are
        # the most likely still resident in any cache hierarchy)
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}           # block -> live refcount
        self._hash2block: Dict[int, int] = {}    # chained key -> block
        self._block2hash: Dict[int, int] = {}
        # block -> its block_size token ids: lookup() verifies a hit
        # against these, so a 64-bit key collision degrades to a cache
        # MISS instead of silently mapping another sequence's KV
        self._block_tokens: Dict[int, Tuple[int, ...]] = {}
        # refcount-0 registered blocks, insertion order = LRU release order
        self._evictable: "OrderedDict[int, None]" = OrderedDict()
        # block -> registering tenant; tenant -> registered-block count
        self._block_tenant: Dict[int, str] = {}
        self._tenant_cached: Dict[str, int] = {}
        self.evictions = 0
        # host offload tier (ISSUE 16): installed by PagedKVCache when
        # FLAGS_serving_offload is on. `offload_capture(b)` returns the
        # per-leaf device slices of block b (the cache owns device I/O —
        # this module stays jax-free); `offload.put` accepts them.
        self.offload = None
        self.offload_capture = None
        # fleet cache directory (ISSUE 17): the router subscribes these
        # so its CacheDirectory learns which replica holds which chain
        # key. `notify_register(key)` fires when a key becomes device-
        # resident; `notify_unregister(key)` when it leaves the device
        # WITHOUT surviving in the host tier (the tier's own on_drop
        # covers the host side) — an entry can then never be
        # stale-authoritative, only stale-missing, which pulls degrade
        # from safely. None = no listener.
        self.notify_register = None
        self.notify_unregister = None

    @property
    def free_blocks(self) -> int:
        """Blocks allocatable RIGHT NOW: the free list plus the refcount-0
        cached blocks eviction can reclaim."""
        return len(self._free) + len(self._evictable)

    @property
    def cached_blocks(self) -> int:
        return len(self._hash2block)

    @property
    def blocks_in_use(self) -> int:
        return len(self._ref)

    def blocks_for(self, kv_tokens: int) -> int:
        """Physical blocks needed to hold ``kv_tokens`` KV entries."""
        return max(1, math.ceil(kv_tokens / self.block_size))

    def can_alloc(self, n: int) -> bool:
        return n <= self.free_blocks

    def alloc(self, n: int) -> List[int]:
        if n > self.free_blocks:
            raise RuntimeError(f"out of KV blocks: want {n}, "
                               f"free {self.free_blocks}")
        blocks = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:                                # LRU-evict a cached block
                b, _ = self._evictable.popitem(last=False)
                self._offload(b)
                self._unregister(b)
                self.evictions += 1
            self._ref[b] = 1
            blocks.append(b)
        return blocks

    def _offload(self, b: int) -> None:
        """Swap a dying registered block into the host tier (when one is
        attached) — called at both eviction sites, BEFORE the block's
        registration (key + verified tokens) is dropped. Blocks without
        stored tokens are skipped: the tier's verified-hit contract needs
        them."""
        if self.offload is None or self.offload_capture is None:
            return
        key = self._block2hash.get(b)
        toks = self._block_tokens.get(b)
        if key is not None and toks is not None:
            self.offload.put(key, toks, self.offload_capture(b))

    def _unregister(self, b: int) -> None:
        """Drop block ``b``'s prefix-cache registration (hash maps, stored
        tokens, tenant accounting). The caller owns what happens to the
        block itself."""
        key = self._block2hash.pop(b)
        del self._hash2block[key]
        if self.notify_unregister is not None and \
                not (self.offload is not None and self.offload.holds(key)):
            # both eviction sites _offload() BEFORE _unregister(), so a
            # key the tier accepted is still replica-resident — the
            # directory entry survives the swap-out
            self.notify_unregister(key)
        self._block_tokens.pop(b, None)
        t = self._block_tenant.pop(b, None)
        if t is not None:
            self._tenant_cached[t] -= 1
            if not self._tenant_cached[t]:
                del self._tenant_cached[t]

    def tenant_cached(self, tenant: str) -> int:
        """Registered prefix-cache blocks currently charged to a tenant."""
        return self._tenant_cached.get(tenant, 0)

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            if self._ref.get(b, 0) <= 0:
                raise RuntimeError(f"double/foreign free of block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                if b in self._block2hash:        # stays cached, evictable
                    self._evictable[b] = None
                else:
                    self._free.append(b)

    # ---- prefix cache ------------------------------------------------------

    def lookup(self, key: int,
               tokens: Optional[Tuple[int, ...]] = None) -> Optional[int]:
        """The cached block for a chained content key, or None. With
        ``tokens`` (the candidate block's ids) the hit is VERIFIED — an
        O(block_size) compare per block, so a hash collision can only
        cost a miss, never map another sequence's KV."""
        b = self._hash2block.get(key)
        if b is not None and tokens is not None \
                and self._block_tokens.get(b) != tokens:
            return None                          # unverifiable == miss
        return b

    def share(self, block: int) -> int:
        """Take a reference on a cached block (a prefix-cache hit mapping
        it into another sequence's table)."""
        if block in self._evictable:             # revive from the LRU list
            del self._evictable[block]
            self._ref[block] = 1
        elif self._ref.get(block, 0) > 0:
            self._ref[block] += 1
        else:
            raise RuntimeError(f"share of unknown block {block}")
        return block

    def register(self, key: int, block: int,
                 tokens: Optional[Tuple[int, ...]] = None,
                 tenant: Optional[str] = None) -> None:
        """Content-hash a LIVE full block for prefix sharing. First writer
        wins: an already-registered key (another sequence beat us to the
        same prefix) or block is left alone. ``tokens`` (the block's ids)
        back :meth:`lookup`'s hit verification; without them a verified
        lookup of this key reports a miss.

        With a ``tenant_quota`` set and a ``tenant`` given, a tenant at
        its quota recycles its OWN least-recently-released refcount-0
        entry to make room — and when every one of its entries is still
        referenced, the registration is simply skipped (the block stays
        usable, just unshared). Either way the tenant cannot push another
        tenant's entries off the LRU list by flooding unique prompts."""
        if key in self._hash2block or block in self._block2hash:
            return
        if self._ref.get(block, 0) <= 0:
            raise RuntimeError(f"register of non-live block {block}")
        if self.tenant_quota is not None and tenant is not None and \
                self._tenant_cached.get(tenant, 0) >= self.tenant_quota:
            mine = next((b for b in self._evictable
                         if self._block_tenant.get(b) == tenant), None)
            if mine is None:
                return                   # quota full of pinned entries
            del self._evictable[mine]
            self._offload(mine)
            self._unregister(mine)
            self._free.append(mine)
            self.evictions += 1
        if self.offload is not None:
            # the device copy becomes the resident tier for this key — a
            # stale host copy must not survive (device XOR host residency)
            self.offload.discard(key)
        self._hash2block[key] = block
        self._block2hash[block] = key
        if tokens is not None:
            self._block_tokens[block] = tokens
        if self.notify_register is not None:
            self.notify_register(key)
        if tenant is not None:
            self._block_tenant[block] = tenant
            self._tenant_cached[tenant] = \
                self._tenant_cached.get(tenant, 0) + 1


class PagedKVCache:
    """The device block pool + its host bookkeeping, per serving engine.

    ``tables`` is the ``[max_slots, W]`` int32 block-table matrix shipped
    with every decode dispatch (W = ceil(max_model_len / block_size));
    unassigned entries point at the null block 0 and are masked by the
    sequence-length mask on device.

    **Cache groups.** A family whose layers do not all see the same
    positions declares its groups (``paged_cache_groups(cfg, block_size)``
    of its module, one entry a group: ``None`` for a group that holds
    every position, the entries of its RING for a group bounded by a
    window). Each group has its own stretch of a table row, side by side
    (``W`` is their sum), and every group draws from the ONE
    :class:`BlockManager`: a sequence of ``p`` pages holds ``p`` blocks in
    an unbounded group and ``min(p, ring)`` in a bounded one, whose page
    ``p`` lives at entry ``p % ring`` (so its blocks are reused in place
    and it never grows past its ring). :meth:`blocks_for` is what a
    sequence costs over all its groups. A request's ``blocks`` stay one
    flat list, in the order they were taken: page by page, and within a
    page group by group. A family that declares nothing has one unbounded
    group, and every number here is what it was.
    """

    def __init__(self, model_config, max_slots: int, max_model_len: int,
                 block_size: int, num_blocks: int = 0, dtype=None,
                 prefix_cache: bool = True,
                 tenant_quota: Optional[int] = None, kv_quant=None,
                 mesh=None, offload: bool = False,
                 offload_blocks: int = 0):
        from ...models import paged_family
        self.block_size = int(block_size)
        self.max_model_len = int(max_model_len)
        self.prefix_cache = bool(prefix_cache)
        self.kv_quant = kv_quant
        # serving tensor parallelism (ISSUE 12): with a mesh, the pool
        # leaves are emitted sharded on their kv-heads axis over the "tp"
        # axis — every HOST structure here (block manager, tables, prefix
        # keys over token ids) is device-count-AGNOSTIC: block ids are
        # global, tables replicate, only pool bytes split across devices
        self.mesh = mesh
        self.tp = int(mesh.shape["tp"]) if mesh is not None else 1
        family = paged_family(model_config)
        groups = getattr(family, "paged_cache_groups", None)
        # one entry a group: None (every position) or its ring's entries
        self.rings: Tuple[Optional[int], ...] = (
            tuple(groups(model_config, self.block_size)) if groups
            else (None,))
        self.uniform = self.rings == (None,)
        pages = max(1, math.ceil(max_model_len / block_size))
        widths = [pages if r is None else int(r) for r in self.rings]
        # the first table column of each group; the row's width
        self.group_start = [sum(widths[:g]) for g in range(len(widths))]
        self.blocks_per_seq = sum(widths)
        if num_blocks <= 0:
            # auto-size: every slot can hold a full-length sequence, +1 null
            num_blocks = max_slots * self.blocks_per_seq + 1
        # kv_quant="int8": int8 K/V blocks + per-token-per-head fp32 scale
        # planes ride in the same pool pytree — every host-side structure
        # here (block manager, tables, prefix-cache keys over TOKEN IDS)
        # is layout-agnostic, so int8 blocks hash/hit/evict exactly like
        # fp blocks; only the device pool layout changes
        self.pool: Dict = family.init_paged_pool(model_config, num_blocks,
                                                 block_size, dtype,
                                                 kv_quant=kv_quant, mesh=mesh)
        self.manager = BlockManager(num_blocks, block_size,
                                    tenant_quota=tenant_quota)
        self.tables = np.zeros((max_slots, self.blocks_per_seq), np.int32)
        # host offload tier (ISSUE 16): evicted registered blocks swap to
        # a bounded host pool instead of dying; admit() restores them
        self.offload = None
        if offload and prefix_cache and offload_blocks > 0:
            from .offload import HostOffloadTier
            self.offload = HostOffloadTier(offload_blocks, block_size)
            self.manager.offload = self.offload
            self.manager.offload_capture = self.read_block

    @property
    def free_blocks(self) -> int:
        return self.manager.free_blocks

    # ---- what a sequence costs, over all groups ----------------------------

    def _cost(self, pages: int) -> int:
        """Blocks a sequence of ``pages`` pages holds over its groups."""
        return sum(pages if r is None else min(pages, r)
                   for r in self.rings)

    def blocks_for(self, kv_tokens: int) -> int:
        """Physical blocks a sequence of ``kv_tokens`` KV entries holds
        over ALL its groups."""
        return self._cost(self.manager.blocks_for(kv_tokens))

    def _pages_held(self, n_blocks: int) -> int:
        """Pages of a sequence that holds ``n_blocks``: the least ``p``
        with ``_cost(p) >= n_blocks`` (the cost grows with the pages, so
        a bisection; ``n_blocks`` itself where one group holds them all)."""
        if self.uniform:
            return n_blocks
        lo, hi = 0, n_blocks
        while lo < hi:
            mid = (lo + hi) // 2
            if self._cost(mid) < n_blocks:
                lo = mid + 1
            else:
                hi = mid
        return lo

    def window_blocks(self, n_blocks: int) -> int:
        """Of a sequence's ``n_blocks``, those its bounded groups hold."""
        pages = self._pages_held(n_blocks)
        return sum(min(pages, r) for r in self.rings if r is not None)

    def _columns(self, page_from: int, page_to: int) -> List[int]:
        """Table columns of the blocks taken for pages ``[page_from,
        page_to)``, in the flat list's order."""
        return [self.group_start[g] + p
                for p in range(page_from, page_to)
                for g, r in enumerate(self.rings) if r is None or p < r]

    # ---- device block I/O --------------------------------------------------

    def read_block(self, block: int) -> Dict:
        """Per-leaf device slices of one physical block (``pool[leaf][:,
        b]`` — the copy is DISPATCHED here, not materialized: np.asarray
        on a returned slice blocks for the D2H). Shared by the offload
        tier's swap-out capture and migration's chain serialization.

        The block index crosses as a DEVICE scalar: a python int bakes
        into the sliced executable as a constant, so a churning tier
        would compile one slice program per distinct block index
        (measured ~50ms each on XLA:CPU — dwarfing the copy itself)."""
        import jax
        import jax.numpy as jnp  # local: module stays jax-free at import

        b = jnp.asarray(block, jnp.int32)
        return {name: jax.lax.dynamic_index_in_dim(arr, b, axis=1,
                                                   keepdims=False)
                for name, arr in self.pool.items()}

    def write_block(self, block: int, data: Dict) -> None:
        """H2D-write one physical block's per-leaf host arrays back into
        the pool — the offload tier's swap-in restore. Same device-scalar
        index discipline as ``read_block`` (one compiled update program
        for every block index, not one per index)."""
        import jax
        import jax.numpy as jnp  # local: module stays jax-free at import

        b = jnp.asarray(block, jnp.int32)
        for name, arr in self.pool.items():
            self.pool[name] = jax.lax.dynamic_update_index_in_dim(
                arr, jnp.asarray(data[name], arr.dtype), b, axis=1)

    def write_blocks(self, blocks: List[int], data: Dict) -> None:
        """H2D-write a gathered run of blocks (``data[leaf]`` carries the
        block axis at position 1: ``[L, len(blocks), ...]``) — the
        migration adopt path's bulk restore."""
        idx = np.asarray(blocks, np.int32)
        for name, arr in self.pool.items():
            self.pool[name] = arr.at[:, idx].set(data[name])

    # ---- admission ---------------------------------------------------------

    def admit(self, ids: np.ndarray,
              reserve_kv: Optional[int] = None,
              namespace: Optional[str] = None
              ) -> Optional[Tuple[List[int], int, Tuple[int, Optional[int]]]]:
        """Map + allocate blocks for a sequence entering prefill.

        ``ids`` are the tokens prefill will compute (the prompt, or prompt
        + already-generated tokens on post-preemption readmission). With
        the prefix cache on, the longest chain of cached full blocks over
        ``ids[:-1]`` is SHARED into the sequence (capped one token short of
        the whole sequence so at least one token always runs through
        prefill — the next-token logits have to come from somewhere); only
        the remainder is allocated. ``reserve_kv`` switches to the legacy
        worst-case reservation (allocate the full ``prompt + max_new - 1``
        footprint now — the ``preempt=False`` mode). ``namespace``
        (ISSUE 19) is the request's adapter id — it seeds the content
        chain so adapter KV and base KV never cross-hit (see
        :func:`prefix_block_chain`). Returns ``(blocks,
        hit_tokens, reg_state)`` — ``reg_state`` seeds
        :meth:`register_prefix` at the hit boundary so later registration
        never re-hashes the hit chain — or None when the pool can't cover
        it right now (the request stays queued; admission never preempts
        running work).
        """
        n_tokens = int(reserve_kv) if reserve_kv is not None else len(ids)
        n_total = self.blocks_for(n_tokens)
        if n_total > self.blocks_per_seq:
            raise ValueError(
                f"sequence needs {n_total} blocks ({n_tokens} KV entries) "
                f"but max_model_len {self.max_model_len} caps block tables "
                f"at {self.blocks_per_seq}")
        hits: List[int] = []
        last_key: Optional[int] = None
        if self.prefix_cache:
            # pin-as-we-go: each hit is share()d the moment it verifies, so
            # a host-tier restore's alloc (which may itself LRU-evict) can
            # never evict a block we are about to map
            for key, toks in prefix_block_chain(ids, self.block_size,
                                                len(ids) - 1,
                                                namespace=namespace):
                b = self.manager.lookup(key, toks)
                if b is not None:
                    self.manager.share(b)
                    hits.append(b)
                    last_key = key
                    continue
                if self.offload is not None and self.manager.can_alloc(1):
                    # device miss — consult the host tier. A verified take
                    # H2D-restores the block and re-registers the key: the
                    # chain continues with zero recompute. A miss (absent,
                    # evicted, or checksum-failed) breaks to the recompute
                    # path exactly as before the tier existed.
                    data = self.offload.take(key, toks)
                    if data is not None:
                        [b] = self.manager.alloc(1)
                        self.write_block(b, data)
                        self.manager.register(key, b, toks)
                        self.offload.swap_ins += 1
                        hits.append(b)
                        last_key = key
                        continue
                break
        n_new = n_total - len(hits)
        if not self.manager.can_alloc(n_new):
            if hits:
                self.manager.free(hits)
            return None
        return (hits + self.manager.alloc(n_new),
                len(hits) * self.block_size, (len(hits), last_key))

    def extend(self, slot: int, blocks: List[int],
               kv_tokens: int) -> Optional[List[int]]:
        """Grow a slot's block list (in place) to cover ``kv_tokens`` KV
        entries — the on-demand decode path. Returns the newly allocated
        blocks ([] when already covered), or None when the pool is dry
        (the engine then preempts)."""
        n = self.blocks_for(kv_tokens) - len(blocks)
        if n <= 0:
            return []
        if not self.manager.can_alloc(n):
            return None
        new = self.manager.alloc(n)
        self.tables[slot, self._columns(
            self._pages_held(len(blocks)),
            self.manager.blocks_for(kv_tokens))] = new
        blocks.extend(new)
        return new

    def register_prefix(self, ids, blocks: List[int], upto: int,
                        state: Tuple[int, Optional[int]] = (0, None),
                        base: int = 0, tenant: Optional[str] = None,
                        namespace: Optional[str] = None
                        ) -> Tuple[int, Optional[int]]:
        """Register the full blocks covering KV entries ``[..upto)`` (those
        the device has finished writing) in the prefix cache,
        INCREMENTALLY: ``state`` is ``(blocks already registered, chained
        key of the last one)`` from the previous call (or ``admit``'s hit
        boundary), so each block's tokens are hashed exactly once over a
        sequence's lifetime — a per-dispatch full-chain re-hash would make
        the continuous-batching host loop O(seq_len^2) per request. For
        the same reason ``ids`` may be just the not-yet-registered TAIL
        with ``base`` naming its first KV position (``ids[p - base]``
        backs entry ``p``). Returns the advanced state; the caller keeps
        it on the request."""
        if not self.prefix_cache:
            return state
        n, h = state
        for key, toks in prefix_block_chain(ids, self.block_size, upto,
                                            start=n, prev_key=h, base=base,
                                            namespace=namespace):
            self.manager.register(key, blocks[n], toks, tenant=tenant)
            n, h = n + 1, key
        return (n, h)

    def assign(self, slot: int, blocks: List[int]) -> None:
        self.tables[slot] = 0
        self.tables[slot, self._columns(
            0, self._pages_held(len(blocks)))] = blocks

    def release(self, slot: int, blocks: List[int]) -> None:
        self.manager.free(blocks)
        self.tables[slot] = 0

    def kv_bytes(self, per_shard: bool = False) -> int:
        """Device bytes the pool holds — every leaf (K + V, plus the scale
        planes on quantized layouts), the number capacity planning and the
        ``kv_pool_bytes`` ops field report. ``per_shard=True`` returns the
        bytes ONE device holds under tensor parallelism (the global total
        divided by the TP degree — the kv-heads split is exact): the
        number a per-chip HBM budget must cover, and the
        ``kv_pool_shard_bytes`` ops field."""
        total = sum(a.size * a.dtype.itemsize for a in self.pool.values())
        return total // self.tp if per_shard else total
