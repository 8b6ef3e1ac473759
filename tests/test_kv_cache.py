"""The seam between ``LLMEngine`` (the scheduler) and ``KVCache`` (the
pools and everything that indexes them), tested on the manager alone: a
toy model's ``init_block_pool`` and no compiled program, so each case
runs in well under a second. The last case is the engine's call helper,
which needs the programs.
"""
import collections
import itertools

import numpy as onp
import pytest

from mxnet_tpu.serving.kv_cache import KVCache
from mxnet_tpu.serving.llm import LLMEngine, LLMMetrics

BS = 4
_seq = itertools.count()
_models = {}


def _model(kind):
    """A toy of each cache geometry: K/V rows in blocks, or one state a
    slot."""
    if kind not in _models:
        from mxnet_tpu.gluon.model_zoo import bert, brumby

        onp.random.seed(0)
        if kind == "kv_blocks":
            net = bert.gpt_like(vocab_size=37, units=16, hidden_size=32,
                                num_layers=2, num_heads=4, max_length=64,
                                dropout=0.0)
        else:
            net = brumby.brumby_like(
                vocab_size=37, units=32, hidden_size=48, num_layers=2,
                num_heads=4, num_kv_heads=2, head_dim=8, max_length=8192,
                prefill_chunk=16)
        net.initialize()
        _models[kind] = net
    return _models[kind]


def _cache(kind="kv_blocks", num_blocks=8, dtype="float32", **kw):
    model = _model(kind)
    geom = model.cache_geometry(BS)
    assert geom.kind == kind
    return KVCache(
        model, geom, num_blocks=num_blocks, block_size=BS,
        kv_cache_dtype=dtype if kind == "kv_blocks" else None,
        metrics=LLMMetrics(f"kvtest{next(_seq)}"), **kw)


def _prompt(n, seed=0):
    return onp.random.RandomState(seed).randint(0, 37, (n,)).astype(onp.int32)


def _serve(kv, prompt, n_tokens):
    """A request's whole life with nothing prefilled: its blocks."""
    res = kv.reserve(prompt, n_tokens)
    kv.commit(res, len(prompt))
    kv.release(res.blocks)
    return res


def _consistent(kv, held=()):
    """free + in use == num_blocks, and every refcount is the lanes that
    hold the block plus its residency in the index."""
    want = collections.Counter(b for res in held for b in res.blocks)
    want.update(kv.prefix.values())
    assert kv.ref == dict(want)
    assert not set(kv.free) & set(kv.ref)
    assert len(set(kv.free)) == len(kv.free)
    assert kv.free_blocks + len(kv.ref) == kv.num_blocks
    assert kv.blocks_in_use == len(kv.ref)


# (1)
@pytest.mark.parametrize("kind", ["kv_blocks", "state_slots"])
def test_reserve_then_release_leaves_the_pool_as_it_was(kind):
    kv = _cache(kind, num_blocks=6)
    a = kv.reserve(_prompt(9), 14)
    b = kv.reserve(_prompt(3, seed=1), 5)
    assert a is not None and b is not None
    assert len(a.blocks) == kv.geom.blocks_for(14)
    assert not set(a.blocks) & set(b.blocks)
    assert kv.trash not in a.blocks + b.blocks
    _consistent(kv, [a, b])
    kv.release(a.blocks)
    kv.release(b.blocks)
    assert sorted(kv.free) == list(range(6)) and kv.ref == {}
    assert kv.metrics.pool_free.get() == 6


# (2)
def test_own_allocation_never_evicts_the_hits_it_has_pinned():
    """A pool exactly as large as the request: three cached blocks and one
    free. A prompt that shares the first two and needs two fresh ones
    must take the free block and evict the THIRD resident — the two it
    shares are older in LRU order, and pinned."""
    kv = _cache(num_blocks=4, prefix_cache=True)
    first = _prompt(13)
    x, y, z = _serve(kv, first, 16).blocks[:3]
    assert list(kv.prefix.values()) == [x, y, z] and kv.free_blocks == 1
    second = first.copy()
    second[8:] = (second[8:] + 1) % 37          # third block differs
    res = kv.reserve(second, 16)
    assert res is not None and res.n_hit == 2
    assert res.blocks[:2] == [x, y] and len(set(res.blocks)) == 4
    assert z in res.blocks[2:]                  # evicted, handed back out
    assert list(kv.prefix.values()) == [x, y]
    assert kv.ref[x] == kv.ref[y] == 2
    _consistent(kv, [res])


# (3)
def test_eviction_is_lru_over_cache_only_residents():
    kv = _cache(num_blocks=6, prefix_cache=True)
    p1, p2, p3 = (_prompt(5, seed=s) for s in (1, 2, 3))
    h1, h2, h3 = (_serve(kv, p, 8).hashes[0] for p in (p1, p2, p3))
    kv.release(kv.reserve(p1, 8).blocks)         # LRU bump: h2, h3, h1
    assert list(kv.prefix) == [h2, h3, h1]
    live = kv.reserve(p3, 8)                     # h3's block at refcount 2
    assert live.n_hit == 1 and kv.free_blocks == 2
    assert list(kv.prefix) == [h2, h1, h3]       # and bumped in its turn
    before = int(kv.metrics.prefix_evictions.value)
    got = kv.reserve(_prompt(3, seed=4), 12)     # 3 blocks: one eviction
    assert list(kv.prefix) == [h1, h3]           # the oldest went
    more = kv.reserve(_prompt(3, seed=5), 4)     # 1 block: h1 goes too
    assert list(kv.prefix) == [h3]
    assert kv.reserve(_prompt(3, seed=6), 4) is None    # h3 is held: stays
    assert list(kv.prefix) == [h3]
    assert int(kv.metrics.prefix_evictions.value) - before == 2
    assert kv.ref[live.blocks[0]] == 2
    _consistent(kv, [live, got, more])


# (4)
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_spilled_rows_come_back_byte_exact(dtype):
    kv = _cache(num_blocks=4, dtype=dtype, prefix_cache=True, kv_spill=True,
                kv_spill_bytes=1 << 20)
    try:
        prompt = _prompt(13)
        res = kv.reserve(prompt, 16)
        rng = onp.random.RandomState(7)
        ids = onp.asarray(res.blocks[:3])
        wrote = []
        for i, pool in enumerate(kv.pools[0]):   # what a prefill would write
            rows = rng.randint(-100, 100, (pool.shape[0], 3) + pool.shape[2:])
            rows = rows.astype(pool.dtype)
            kv.pools[0][i] = pool.at[:, ids].set(rows)
            wrote.append(rows)
        kv.commit(res, 13)
        kv.release(res.blocks)
        _serve(kv, _prompt(13, seed=9), 16)      # floods the pool: evicts
        assert kv.spill.level()[0] == 3
        assert not set(res.hashes) & set(kv.prefix)
        back = kv.reserve(prompt, 16)
        assert back.n_hit == 3
        for got, want in zip(kv.snapshot(back.blocks[:3]), wrote):
            got = onp.asarray(got)
            assert got.dtype == want.dtype and onp.array_equal(got, want)
        assert [kv.prefix[h] for h in res.hashes] == back.blocks[:3]
        _consistent(kv, [back])
    finally:
        kv.close()


# (5)
def test_a_wholly_cached_prompt_leaves_its_last_block_to_run():
    kv = _cache(prefix_cache=True)
    prompt = _prompt(12)                        # three full blocks
    assert _serve(kv, prompt, 16).n_hit == 0
    assert len(kv.prefix) == 3
    again = kv.reserve(prompt, 16)
    assert again.n_hit == 2                     # the last token must run
    assert again.blocks[:2] == [kv.prefix[h] for h in again.hashes[:2]]
    assert again.blocks[2] != kv.prefix[again.hashes[2]]


# (6)
@pytest.mark.parametrize("kind", ["kv_blocks", "state_slots"])
def test_a_reservation_that_cannot_be_covered_changes_nothing(kind):
    prefix = {"prefix_cache": True} if kind == "kv_blocks" else {}
    kv = _cache(kind, num_blocks=4, **prefix)
    prompt = _prompt(13)
    held = [kv.reserve(prompt, 16)]
    kv.commit(held[0], 13)
    while kv.free_blocks:                       # a state: a slot a lane
        held.append(kv.reserve(_prompt(2, seed=len(held)), 3))
    ref, index = dict(kv.ref), list(kv.prefix.items())
    assert kv.reserve(prompt, 16) is None       # its hits were pinned ...
    assert kv.ref == ref and kv.free_blocks == 0        # ... and let go
    assert list(kv.prefix.items()) == index
    _consistent(kv, held)


# (7)
@pytest.mark.parametrize("kind", ["kv_blocks", "state_slots"])
def test_reset_gives_a_new_pool_and_keeps_the_spill_tier(kind):
    spill = (dict(prefix_cache=True, kv_spill=True, kv_spill_bytes=1 << 20)
             if kind == "kv_blocks" else {})
    kv = _cache(kind, num_blocks=4, **spill)
    try:
        for pair in kv.pools:
            pair[:] = [p + 1 for p in pair]
        _serve(kv, _prompt(13), 16)
        held = kv.reserve(_prompt(13, seed=1), 16)   # evicts into the tier
        spilled = kv.spill.level() if kv.spill else None
        kv.release(held.blocks)                 # reset() finds no holder
        kv.reset()
        assert sorted(kv.free) == list(range(4))
        assert kv.ref == {} and len(kv.prefix) == 0
        assert all(not onp.asarray(p).any() for p in kv.pools[0])
        assert kv.pools[0][0].shape[1] == 5     # the trash block with it
        if kv.spill:
            assert spilled[0] > 0 and kv.spill.level() == spilled
    finally:
        kv.close()


# (8)
def test_a_state_reserves_one_slot_whatever_the_length():
    kv = _cache("state_slots", num_blocks=3)
    held = [kv.reserve(_prompt(n), n + extra)
            for n, extra in ((1, 1), (100, 28), (5000, 3000))]
    assert [len(r.blocks) for r in held] == [1, 1, 1]
    assert all(r.n_hit == 0 and r.hashes == [] for r in held)
    assert kv.free_blocks == 0
    _consistent(kv, held)


@pytest.mark.parametrize("feature", [
    dict(prefix_cache=True), dict(prefix_cache=True, kv_spill=True),
    dict(role="decode"), dict(draft_model="a draft"), dict(mesh="a mesh")])
def test_a_state_refuses_loudly_what_blocks_of_rows_accept(feature):
    """No feature the block geometry carries is dropped in silence."""
    with pytest.raises(ValueError, match="not supported with a state_slots"):
        _cache("state_slots", **feature)


def test_a_state_is_held_in_its_own_dtype():
    with pytest.raises(ValueError, match="kv_cache_dtype 'int8'"):
        KVCache(_model("state_slots"),
                _model("state_slots").cache_geometry(BS), num_blocks=2,
                block_size=BS, kv_cache_dtype="int8",
                metrics=LLMMetrics(f"kvtest{next(_seq)}"))


# (9)
@pytest.mark.parametrize("kind", ["kv_blocks", "state_slots"])
def test_200_operations_keep_the_books(kind):
    prefix = {"prefix_cache": True} if kind == "kv_blocks" else {}
    kv = _cache(kind, num_blocks=12, **prefix)
    rng = onp.random.RandomState(31)
    prompts = [_prompt(int(rng.randint(1, 20)), seed=s) for s in range(6)]
    held, refused = [], 0
    for _ in range(200):
        if held and rng.rand() < 0.45:
            kv.release(held.pop(int(rng.randint(len(held)))).blocks)
        else:
            prompt = prompts[int(rng.randint(len(prompts)))]
            res = kv.reserve(prompt, len(prompt) + int(rng.randint(1, 9)))
            if res is None:
                refused += 1
            else:
                if rng.rand() < 0.9:            # the rest: a failed prefill
                    kv.commit(res, len(prompt))
                held.append(res)
        _consistent(kv, held)
    assert refused and len(held)                # both branches were walked
    for res in held:
        kv.release(res.blocks)
    _consistent(kv)
    assert kv.free_blocks + kv.evictable() == 12


# (10)
def test_the_call_helper_records_one_entry_a_program():
    """Warm-up and the serving path call the same object: whichever comes
    first records the program's manifest entry, the other adds none."""
    def entries(eng):
        return sorted((e["label"], e["bucket"], e["dtype"])
                      for e in eng.warmup_manifest().entries())

    kw = dict(max_running=2, block_size=BS, max_context=32,
              kv_cache_dtype="float32")
    net = _model("kv_blocks")
    with LLMEngine(net, **kw) as warmed, LLMEngine(net, **kw) as cold:
        warmed.warmup(prompt_lengths=[5])
        at_warmup = entries(warmed)
        assert at_warmup == [("llm.decode", 2, "float32"),
                             ("llm.prefill", 8, "float32")]
        assert warmed.stats()["counters"]["compiles"] == 2
        for eng in (warmed, cold):
            assert len(eng.generate(_prompt(5), 3)) == 3
        assert entries(warmed) == entries(cold) == at_warmup
        assert cold.stats()["counters"]["compiles"] == 2
        warmed.warmup(prompt_lengths=[5])       # nothing is fresh any more
        assert entries(warmed) == at_warmup
        assert not warmed._decode.fresh and not cold._decode.fresh
