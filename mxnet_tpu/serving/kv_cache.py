"""``KVCache`` — the KV-cache manager under :class:`~.llm.LLMEngine`: the
pools and everything that indexes them.

The scheduler (:mod:`.llm`) knows requests, lanes, lane tables and which
program to run; this module knows what a pool block is
(:class:`~mxnet_tpu.gluon.model_zoo.generation.CacheGeometry`: rows in
blocks, one state a slot, or rows in blocks with a state a lane beside
them), which blocks are free, who holds each
(refcounts), which hold a cached prefix (the chain-hash index, LRU),
what to evict and where evicted rows go (the spill tiers of
:mod:`.kv_spill`) and how they come back (re-attach). It is the only
code that indexes a pool's block axis outside a compiled program, and it
imports nothing from :mod:`.llm`.

A request's life here: :meth:`KVCache.reserve` (lookup, pin, allocate,
re-attach) -> the scheduler prefills -> :meth:`KVCache.commit` (index the
fresh full blocks, export them from a prefill-role engine) -> decode ->
:meth:`KVCache.release`. A fault that may have consumed the donated
pools: :meth:`KVCache.reset`.

See ``docs/llm_serving.md`` ("Modules") for the drawing.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, Dict, List, Optional

import jax
import numpy as onp

from . import kv_hash

__all__ = ["KVCache", "Reservation"]


# donate the pool buffer: the scatter updates HBM in place (a DMA of
# the restored rows), never a functional copy of the whole pool
_pool_scatter = jax.jit(
    lambda pool, idx, rows: pool.at[:, idx].set(rows),
    donate_argnums=(0,))

# batched block-row gather for spill demotion (one D2H per pool per
# eviction wave, not one per block)
_pool_gather = jax.jit(lambda pool, idx: pool[:, idx])

# a spilled block's payload: the target pair's rows, then the draft
# pair's (speculative decoding shares the block ids)
_PAYLOAD = (("k", "v"), ("dk", "dv"))


@dataclasses.dataclass(frozen=True)
class Reservation:
    """What :meth:`KVCache.reserve` hands the scheduler: the request's
    ``blocks`` (shared hits first, then fresh ones), how many of them
    were hits (``n_hit``: resident or re-attached — prefill skips them)
    and the prompt's chain ``hashes`` (:meth:`KVCache.commit` indexes the
    fresh full blocks under them)."""

    blocks: List[int]
    n_hit: int
    hashes: List[bytes]


class KVCache:
    """The pools of ``model.init_block_pool`` (and a draft model's,
    addressed by the same block ids) with their allocator, prefix index
    and spill tier.

    ``geom`` is the model's ``cache_geometry(block_size)``; ``metrics``
    the engine's ``LLMMetrics`` (the pool, prefix and spill gauges are
    set here, at the moments they change); ``kv_cache_dtype``,
    ``prefix_cache``, the five ``kv_spill*`` and ``role`` are
    :class:`~.llm.LLMEngine`'s arguments of those names as the caller
    gave them (None: the default — ``dtype`` is what was resolved); a
    ``"prefill"`` role exports every committed block through the tier.
    ``pools[0]`` is the target's pools as ``init_block_pool`` returns
    them (``[k, v]``; ``[S, z]``; ``[k, v, S, tail]``), ``pools[1]`` the
    draft's: the programs take them all and give them back (donated), so
    the engine's call helper swaps them here. **Two families.** Where
    ``geom.lane_state``, the pools past the first two are not indexed by
    blocks: they have ``max_running`` slots and the trash slot, a lane's
    slot is the lane's index, and nothing here allocates them —
    ``reserve`` / ``commit`` / ``release`` count blocks of rows only.
    Not thread-safe: the engine's state lock covers every call but
    :meth:`evictable`.
    """

    def __init__(self, model, geom, *, num_blocks: int, block_size: int,
                 kv_cache_dtype, metrics, max_running: int = 0,
                 draft_model=None,
                 prefix_cache: Optional[bool] = None,
                 kv_spill: Optional[bool] = None,
                 kv_spill_bytes: Optional[int] = None,
                 kv_spill_dir: Optional[str] = None,
                 kv_spill_serve: Optional[bool] = None,
                 kv_spill_peers: Optional[List[str]] = None,
                 role: Optional[str] = None, mesh=None):
        from ..gluon.model_zoo.generation import _resolve_cache_dtype

        if role is not None:
            # disaggregated serving (docs/llm_serving.md): both halves
            # speak the chain-hash + shared-codec handoff protocol, so
            # both need the prefix cache and a spill tier. The prefill
            # side SERVES its exported rows; the decode side probes
            # peers (wired later via set_peers).
            if prefix_cache is False or kv_spill is False:
                raise ValueError(
                    f"role={role!r} requires prefix_cache and kv_spill "
                    "(the handoff is keyed by chain hashes and carried "
                    "by the spill tier)")
            prefix_cache = kv_spill = True
            if role == "prefill" and kv_spill_serve is None:
                kv_spill_serve = True
        armed = {"role": role is not None, "mesh": mesh is not None,
                 "draft_model": draft_model is not None,
                 "kv_spill": bool(kv_spill),
                 "prefix_cache": bool(prefix_cache)}
        kind = geom.kind + (" + lane state" if geom.lane_state else "")
        for feature, on in armed.items():
            if on and feature in geom.unsupported:
                raise ValueError(
                    f"{feature} is not supported with a {kind} "
                    f"cache: {geom.unsupported[feature]}")
        if kv_spill and not prefix_cache:
            raise ValueError(
                "kv_spill requires prefix_cache: spilled blocks are "
                "indexed by the prefix cache's chain hashes")
        if geom.cache_dtypes is not None:
            if kv_cache_dtype not in (None, *geom.cache_dtypes):
                raise ValueError(
                    f"kv_cache_dtype {kv_cache_dtype!r} is not supported "
                    f"with a {kind} cache: it is held as "
                    f"{'/'.join(geom.cache_dtypes)} (pass that, or None)")
            kv_cache_dtype = kv_cache_dtype or geom.cache_dtypes[0]
        self.dtype = _resolve_cache_dtype(model, kv_cache_dtype)
        self.geom = geom
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # the state family's slots: a lane's own index, then the trash
        self._lane_slots = {"state_slots": int(max_running) + 1} \
            if geom.lane_state else {}
        self.metrics = metrics
        self.prefix_on = bool(prefix_cache)
        self._models = [model] + ([draft_model] if draft_model is not None
                                  else [])
        self._mesh = mesh
        self._export = role == "prefill"
        # +1 trash block at index num_blocks — retired lanes and pad
        # splices write there, never into a live sequence
        self.trash = self.num_blocks
        # tiered KV spill under the pool (host RAM / disk / remote) —
        # indexed by the SAME chain hashes as the prefix cache
        self.spill = None
        if kv_spill:
            from .kv_spill import (KVSpillTier, spill_dir_from_env,
                                   spill_peers_from_env)

            self.spill = KVSpillTier(
                bytes_limit=kv_spill_bytes,
                root=(kv_spill_dir if kv_spill_dir is not None
                      else spill_dir_from_env()),
                peers=(list(kv_spill_peers) if kv_spill_peers is not None
                       else spill_peers_from_env()),
                serve=bool(kv_spill_serve))
        # per-block refcounts (lane ownership + prefix-cache residency;
        # a block returns to the free list only at refcount zero — the
        # copy-on-write discipline: shared prompt blocks are read-only
        # by construction, divergence starts at the first uncached
        # block, so "copy" never actually copies)
        self.ref: Dict[int, int] = {}
        # chain-hash -> resident block id, LRU-ordered (a radix lookup
        # flattened: the chain hash of block j commits to blocks 0..j,
        # so longest-prefix match is consecutive dict hits)
        self.prefix: "OrderedDict[bytes, int]" = OrderedDict()
        self.hit_requests = 0
        self.reset()

    # -- the pools ---------------------------------------------------------
    def reset(self) -> None:
        """New zeroed pools, a full free list, no refcounts and an empty
        index: the start, and what follows a fault (a failed program call
        may have consumed the donated buffers; the prefix cache indexes
        pool CONTENT, so it goes with the pool). Callers hold no block
        when they call it."""
        self.pools = [
            [self._shard_pool(p._data) for p in m.init_block_pool(
                self.num_blocks + 1, self.block_size, dtype=self.dtype,
                **self._lane_slots)]
            for m in self._models]
        self.free: List[int] = list(range(self.num_blocks))
        self.ref.clear()
        self.prefix.clear()
        # the spill tier SURVIVES the rebuild on purpose: it is
        # content-addressed (chain hash -> exact payload copy), so its
        # entries stay valid after the pool's block ids are reissued —
        # the first post-fault admissions re-attach instead of paying a
        # cold re-prefill
        self.metrics.prefix_cached_blocks.set(0)
        self.metrics.pool_free.set(len(self.free))

    def _shard_pool(self, arr):
        """Commit one KV block pool to the mesh as a global array,
        sharded on its LAST axis (the one pool layout,
        ``(L, NB+1, bs, H*D')``: a row holds the heads side by side, so
        ``tp`` equal parts of a row are contiguous groups of whole
        heads — heads are embarrassingly parallel under paged
        attention, each head's ``D'`` values with their int8
        bitcast-scale tail stay together as long as ``tp`` divides the
        heads, and the block axis stays whole so block ids keep
        addressing the global pool). On a mesh without a ``tp`` axis
        the spec collapses to replication (the ``named_sharding``
        contract)."""
        if self._mesh is None:
            return arr
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import named_sharding

        return jax.device_put(
            arr, named_sharding(P(None, None, None, "tp"), self._mesh))

    def bytes_per_device(self) -> int:
        """Bytes of KV pool resident PER DEVICE — the number that
        decides whether a model fits a chip. Sharded pools divide the
        heads of every row across the mesh, so this is the
        largest-servable-model lever: per-device share = total / tp."""
        total = 0
        for pair in self.pools:
            for arr in pair:
                shards = getattr(arr, "addressable_shards", None)
                total += (int(shards[0].data.nbytes) if shards
                          else int(arr.nbytes))
        return total

    def snapshot(self, blocks: List[int], lane: Optional[int] = None):
        """``blocks`` of the target's two pools (``pool[:, blocks]``) as
        new device arrays; where a lane holds a state beside its blocks,
        ``lane``'s slot of the two state pools instead (the rows are what
        the request's tokens already show)."""
        ids = onp.asarray([lane] if self.geom.lane_state else blocks,
                          onp.int32)
        first, second = self.pools[0][-2:]
        return _pool_gather(first, ids), _pool_gather(second, ids)

    # -- levels ------------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self.free)

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - len(self.free)

    def evictable(self) -> int:
        """Prefix-cache residents nothing else references (refcount 1)
        — blocks :meth:`_alloc` reclaims on demand. Advisory racy read on
        purpose (no scheduler lock): the fleet's free-capacity gauge
        adds this to the free list so an idle prefix-cache engine —
        which keeps served blocks resident instead of returning them —
        doesn't read as permanently saturated to the router's
        quota/deadline-class pressure shed or the autoscaler's
        free-fraction trigger."""
        try:
            return sum(1 for b in list(self.prefix.values())
                       if self.ref.get(b, 0) == 1)
        except RuntimeError:
            return 0            # snapshot raced a resize — next read wins

    # -- the spill tier's wiring -------------------------------------------
    @property
    def endpoint(self) -> Optional[str]:
        """``host:port`` of the spill tier's BlockServer (None unless it
        serves)."""
        return self.spill.endpoint if self.spill is not None else None

    def set_peers(self, peers: List[str]) -> None:
        """(Re)wire the spill tier's remote peers; without a tier there
        is nothing to wire."""
        if self.spill is not None:
            self.spill.set_peers(list(peers))

    def close(self) -> None:
        if self.spill is not None:
            self.spill.close()

    # -- block accounting --------------------------------------------------
    def _incref(self, blk: int) -> None:
        self.ref[blk] = self.ref.get(blk, 0) + 1

    def _decref(self, blk: int) -> None:
        n = self.ref.get(blk, 0) - 1
        if n > 0:
            self.ref[blk] = n
            return
        self.ref.pop(blk, None)
        self.free.append(blk)

    def _alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` blocks off the free list (refcount 1 each),
        evicting LRU prefix-cache entries that nothing else references
        when the list runs short. None when even a drained cache cannot
        cover the reservation."""
        evicted: List[tuple] = []
        while len(self.free) < n and self.prefix:
            for hsh, blk in self.prefix.items():    # LRU order
                if self.ref.get(blk, 0) == 1:       # cache-only resident
                    del self.prefix[hsh]
                    if self.spill is not None:
                        evicted.append((hsh, blk))
                    self.metrics.prefix_evictions.inc()
                    self._decref(blk)
                    break
            else:
                break                               # all cached blocks live
        if evicted:
            # demote instead of drop: the blocks' exact rows park in
            # the host-RAM tier, re-attachable by DMA on the prefix's
            # next admission. Batched on purpose — a freed block's rows
            # stay intact until this _alloc hands it back out below, and
            # eviction runs inside admission, so every per-block D2H
            # dispatch saved here is TTFT shaved off the incoming
            # request.
            self._spill_save(evicted)
        # gauge tracks evictions even when the allocation still fails —
        # free + cached must reconcile during the overload window too
        self.metrics.prefix_cached_blocks.set(len(self.prefix))
        if len(self.free) < n:
            return None
        got = [self.free.pop() for _ in range(n)]
        for b in got:
            self.ref[b] = 1
        return got

    def _spill_level(self) -> None:
        blocks, nbytes = self.spill.level()
        self.metrics.kv_spill_blocks.set(blocks)
        self.metrics.kv_spill_bytes.set(nbytes)

    def _spill_save(self, evicted: List[tuple]) -> None:
        """Copy the ``(hash, block)`` pairs' exact pool rows (and the
        draft pools' when speculative decoding shares the block ids)
        into the spill tier — ONE batched gather + D2H per pool, not a
        dispatch per block. Byte-exact rows are the token-identity
        guarantee: re-attach restores precisely the KV the prefill
        wrote, int8 bitcast-scale layout included."""
        arr = onp.asarray([blk for _, blk in evicted], onp.int32)
        cols = {name: onp.asarray(_pool_gather(pool, arr))
                for names, pair in zip(_PAYLOAD, self.pools)
                for name, pool in zip(names, pair)}
        for i, (hsh, _) in enumerate(evicted):
            self.spill.put(
                hsh, {kk: vv[:, i].copy() for kk, vv in cols.items()})
        self._spill_level()

    def _reattach(self, ids: List[int], payloads: List[Dict],
                  tiers: List[str], hashes: List[bytes]) -> None:
        """Write re-attached payload rows back into freshly allocated
        pool blocks (ONE donated scatter per pool — the donation lets
        XLA update the pool buffer in place, so the cost is the DMA of
        the restored rows, not a functional copy of the whole pool) and
        admit them into the prefix cache as residents."""
        arr = onp.asarray(ids, onp.int32)
        for names, pair in zip(_PAYLOAD, self.pools):
            for i, name in enumerate(names):
                pair[i] = _pool_scatter(
                    pair[i], arr,
                    onp.stack([pl[name] for pl in payloads], axis=1))
        for blk, hsh in zip(ids, hashes):
            if hsh not in self.prefix:
                self.prefix[hsh] = blk
                self._incref(blk)       # cache residency over the lane ref
        for t in tiers:
            self.metrics.count_reattach(t)
        self.metrics.prefix_cached_blocks.set(len(self.prefix))
        self._spill_level()

    # -- a request's life --------------------------------------------------
    def reserve(self, prompt, n_tokens: int,
                hits_usable: Optional[Callable[[int, int], bool]] = None
                ) -> Optional[Reservation]:
        """The blocks of a request whose prompt and answer (and
        speculative slack) come to ``n_tokens``: its worst case, so an
        in-flight sequence never meets an exhausted pool. With the prefix
        cache armed, the prompt's leading full blocks that are resident
        are shared (refcounted, read-only), those parked in a spill tier
        re-attach into fresh blocks, and only the rest is left to
        prefill. ``hits_usable(n, rest)``: whether the scheduler can
        prefill the prompt's ``rest`` tokens behind ``n`` cached blocks
        (its suffix buckets are its own); where it cannot, nothing is
        shared. None
        when even a drained cache cannot cover the reservation: every
        refcount is then as it was."""
        bs = self.block_size
        p = int(prompt.shape[0])
        need = self.geom.blocks_for(n_tokens)
        # prefix-cache lookup: the longest run of resident chain hashes
        # (consecutive dict hits == the radix descent, since hash j
        # commits to the whole prefix through block j)
        hashes: List[bytes] = []
        hit_hashes: List[bytes] = []
        hit_blocks: List[int] = []
        payloads: List[Dict] = []
        tiers: List[str] = []
        if self.prefix_on:
            hashes = kv_hash.chain_hashes(prompt, bs)
            for hsh in hashes:
                blk = self.prefix.get(hsh)
                if blk is None:
                    break
                hit_hashes.append(hsh)
                hit_blocks.append(blk)
            if self.spill is not None and len(hit_blocks) < len(hashes):
                # extend the resident run from the spill tiers: blocks
                # whose content parks in host RAM / disk / a peer
                # re-attach by DMA instead of re-prefilling. Probed in
                # chain order — the hit run must stay consecutive.
                # Remote probes are deadline-bounded and contained
                # (any transport fault reads as a miss).
                for j in range(len(hit_blocks), len(hashes)):
                    payload, tier = self.spill.get(hashes[j])
                    if payload is None:
                        break
                    if len(self.pools) > 1 and ("dk" not in payload
                                                or "dv" not in payload):
                        break   # a draft-less peer payload cannot
                    payloads.append(payload)        # feed draft pools
                    tiers.append(tier)
            run = len(hit_blocks) + len(payloads)
            if run and run * bs == p:
                # the last real token must still run (its logits sample
                # the first generated token): never consume it from cache
                if payloads:
                    payloads.pop()
                    tiers.pop()
                else:
                    hit_blocks.pop()
                    hit_hashes.pop()
                run -= 1
            if run and hits_usable is not None \
                    and not hits_usable(run, p - run * bs):
                hit_blocks, hit_hashes = [], []
                payloads, tiers = [], []
        n_res = len(hit_blocks)             # HBM-resident shared blocks
        n_hit = n_res + len(payloads)       # prefill skipped for these
        # pin the hits BEFORE allocating: _alloc's LRU eviction must
        # never evict (and re-issue) the very blocks this admission is
        # about to share — a pinned block (refcount >= 2) is not
        # evictable
        for blk, hsh in zip(hit_blocks, hit_hashes):
            self._incref(blk)
            self.prefix.move_to_end(hsh)            # LRU bump
        fresh = self._alloc(need - n_res)
        if fresh is None:
            for blk in hit_blocks:
                self._decref(blk)
            return None
        if payloads:
            # re-attach: the first len(payloads) fresh blocks receive
            # the spilled rows and become cache residents
            self._reattach(fresh[:len(payloads)], payloads, tiers,
                           hashes[n_res:n_hit])
        self.metrics.pool_free.set(len(self.free))
        if self.prefix_on:
            self.metrics.observe_prefix(n_hit * bs, p - n_hit * bs)
            if n_hit:
                self.hit_requests += 1
        return Reservation(hit_blocks + fresh, n_hit, hashes)

    def commit(self, res: Reservation, prompt_len: int) -> None:
        """Prefill has landed: admit the prompt's freshly computed full
        blocks into the cache (+1 cache ref each; they are never written
        again — decode writes land at positions >= ``prompt_len``, past
        every full block)."""
        if not self.prefix_on:
            return
        fresh: List[tuple] = []
        for j in range(res.n_hit,
                       min(prompt_len // self.block_size, len(res.hashes))):
            hsh = res.hashes[j]
            if hsh not in self.prefix:
                self.prefix[hsh] = res.blocks[j]
                self._incref(res.blocks[j])
                fresh.append((hsh, res.blocks[j]))
        self.metrics.prefix_cached_blocks.set(len(self.prefix))
        if self._export and fresh:
            # disaggregated handoff: a prefill-role engine EXPORTS
            # every freshly computed full block's rows into its
            # serving spill tier the moment prefill lands — the
            # decode replica fetches them as its "remote" tier and
            # re-attaches by DMA. Export precedes req.finish(), so
            # the router's prefill wait() doubles as the
            # export-complete barrier. (Same batched D2H gather as
            # eviction demotion; an evicted export later reads as a
            # contained miss and the decode side re-prefills.)
            self._spill_save(fresh)
            self.metrics.handoff_exported.inc(len(fresh))

    def release(self, blocks: List[int]) -> None:
        """Drop one reference on each of ``blocks``; a block returns to
        the free list only when its refcount hits zero (prefix-cache
        residents and other lanes sharing a prompt prefix keep theirs
        alive)."""
        for b in blocks:
            self._decref(b)
        self.metrics.pool_free.set(len(self.free))
