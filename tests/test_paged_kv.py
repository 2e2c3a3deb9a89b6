"""Paged KV cache + radix prefix-cache sharing.

The correctness anchor is bit-exactness: serving through block-table
indirection (gather -> unmodified model step -> scatter) must equal the
contiguous per-slot cache bit-for-bit — logits, sampled tokens, AND cache
contents — across block sizes, prompt lengths, bucket edges, and shuffled
physical block layouts. On top of the allocator: a prefix-cache *hit*
(matched blocks mapped with zero prefill compute) must produce exactly the
tokens a cold prefill produces; sharing must be isolation-safe (refcounts +
copy-on-write); and the compile budget must stay at the bucketed-prefill
baseline — table values are traced, so remaps never retrace. Satellites
ride along: the ``advance`` clamp fix (finish_reason "capacity"), page-aware
admission deferral, LRU trie eviction under pool pressure, cache-aware
queue pricing, and hit-rate/cached-token accounting in run stats and
telemetry events."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro import configs
from repro.models import build_model
from repro.models.common import paged_gather
from repro.models.registry import serving_caps
from repro.serve.engine import ContinuousEngine, Request
from repro.serve.paging import (PagePool, RadixPrefixCache,
                                resolve_kv_block_size)
from repro.serve.queue import RequestQueue
from repro.serve.step import (make_decode_step, make_paged_decode_step,
                              make_paged_slot_prefill, make_slot_prefill)

MAX_SEQ = 32


@pytest.fixture(scope="module")
def dense():
    cfg = configs.get_smoke("granite-20b")
    model = build_model(cfg, q_block=8)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def paged_steps(dense):
    _, model, _ = dense
    return (jax.jit(make_slot_prefill(model)),
            jax.jit(make_paged_slot_prefill(model)),
            jax.jit(make_decode_step(model)),
            jax.jit(make_paged_decode_step(model)))


def _mk_reqs(cfg, n, plen=8, max_new=6, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                    max_new_tokens=max_new, **kw) for i in range(n)]


# ---------------------------------------------------------------------------
# block-size resolution


def test_resolve_block_size():
    assert resolve_kv_block_size("auto", 64) == 32
    assert resolve_kv_block_size("auto", 48) == 16
    assert resolve_kv_block_size("auto", 24) == 8
    assert resolve_kv_block_size("auto", 7) is None    # nothing divides
    assert resolve_kv_block_size(None, 64) is None
    assert resolve_kv_block_size("off", 64) is None
    assert resolve_kv_block_size(16, 48) == 16
    with pytest.raises(ValueError):
        resolve_kv_block_size(32, 48)       # must divide max_seq
    # unsupported family: auto degrades silently, explicit raises
    assert resolve_kv_block_size("auto", 64, supported=False) is None
    with pytest.raises(ValueError):
        resolve_kv_block_size(16, 64, supported=False)


# ---------------------------------------------------------------------------
# allocator: refcounts, null block, COW, zero-on-free


def test_pool_alloc_free_refcount():
    pool = PagePool(n_slots=2, n_slot_blocks=2, n_blocks=5, block_size=8)
    assert pool.free_blocks() == 4           # block 0 reserved
    a, b = pool.alloc(), pool.alloc()
    assert a != PagePool.NULL and b != PagePool.NULL and a != b
    pool.retain(a)
    pool.free(a)
    assert pool.free_blocks() == 2           # still referenced once
    pool.free(a)
    assert pool.free_blocks() == 3
    assert a in pool.pending_zero            # must be scrubbed before reuse
    pool.free(b)
    assert sorted(pool.drain_pending_zero()) == sorted([a, b])
    assert pool.pending_zero == []


def test_pool_exhaustion_and_stats():
    pool = PagePool(n_slots=1, n_slot_blocks=3, n_blocks=4, block_size=4)
    got = [pool.alloc() for _ in range(3)]
    assert all(g is not None for g in got)
    assert pool.alloc() is None              # dry, not an exception
    assert pool.stats.peak_used == 3 and pool.stats.allocs == 3
    with pytest.raises(ValueError):
        PagePool(1, 4, 4, 4)                 # can't back one slot + null


def test_pool_shared_mapping_and_cow():
    pool = PagePool(n_slots=2, n_slot_blocks=2, n_blocks=6, block_size=8)
    blk = pool.alloc()
    pool.tables[0, 0] = blk
    pool.map_shared(1, [blk])                # slot 1 shares it
    assert pool.refcount[blk] == 2
    state, b, _ = pool.ensure_writable(0, pos=3)
    assert state == "cow" and b == blk       # shared: writer must copy
    dst = int(pool.tables[0, 0])
    assert dst != blk and pool.refcount[dst] == 1
    assert pool.refcount[blk] == 1           # writer's ref moved to the copy
    assert pool.stats.cow_copies == 1
    # exclusively owned now: plain ok
    assert pool.ensure_writable(0, pos=3)[0] == "ok"
    # unbacked boundary: fresh block
    state, nb, _ = pool.ensure_writable(0, pos=8)
    assert state == "new" and int(pool.tables[0, 1]) == nb
    pool.release_slot(0)
    assert pool.slot_blocks(0) == []
    assert pool.refcount[blk] == 1           # slot 1's ref survives


# ---------------------------------------------------------------------------
# radix trie: match/insert/probe/LRU eviction


def _trie(bs=4, n_blocks=12):
    pool = PagePool(n_slots=1, n_slot_blocks=4, n_blocks=n_blocks,
                    block_size=bs)
    return RadixPrefixCache(bs, pool), pool


def test_trie_match_caps_at_tail():
    trie, pool = _trie(bs=4)
    toks = np.arange(12, dtype=np.int32)
    blocks = [pool.alloc() for _ in range(3)]
    trie.insert(toks, blocks)
    assert len(trie) == 3
    # full 12-token prompt: at least one token must be left for prefill
    assert trie.match(toks) == blocks[:2]
    assert trie.match(np.arange(13, dtype=np.int32)) == blocks[:3]
    assert trie.match(np.arange(4, dtype=np.int32)) == []       # < 1 block + 1
    # diverging token breaks the chain at block granularity
    other = toks.copy()
    other[5] = 99
    assert trie.match(other) == blocks[:1]


def test_trie_probe_has_no_side_effects():
    trie, pool = _trie(bs=4)
    toks = np.arange(9, dtype=np.int32)
    trie.insert(toks, [pool.alloc(), pool.alloc()])
    before = (trie.stats.hits, trie.stats.misses)
    assert trie.probe(toks) == 8
    assert trie.probe(np.arange(100, 105, dtype=np.int32)) == 0
    assert (trie.stats.hits, trie.stats.misses) == before


def test_trie_refcounts_and_eviction():
    trie, pool = _trie(bs=4)
    a = np.arange(8, dtype=np.int32)
    b = np.concatenate([a[:4], np.arange(50, 54, dtype=np.int32)])
    ba = [pool.alloc(), pool.alloc()]
    bb = [ba[0], pool.alloc()]               # b shares a's first block
    trie.insert(a, ba)
    trie.insert(b, bb)     # shared head already cached: first writer wins,
    assert pool.refcount[ba[0]] == 2         # no second trie reference
    assert pool.refcount[bb[1]] == 2         # alloc's ref + the trie's
    # simulate the computing requests releasing their own refs
    for blk in set(ba + bb):
        pool.free(blk)
    assert trie.evictable_blocks() == 3      # trie is now the sole owner
    free0 = pool.free_blocks()
    assert trie.evict(1) == 1                # LRU leaf goes first
    assert pool.free_blocks() == free0 + 1
    assert trie.evict(10) == 2               # rest drains leaves-first
    assert len(trie) == 0
    assert trie.stats.evictions == 3


def test_trie_shared_block_not_evictable():
    trie, pool = _trie(bs=4)
    toks = np.arange(8, dtype=np.int32)
    blocks = [pool.alloc(), pool.alloc()]
    trie.insert(toks, blocks)                # refcount 2 (alloc + trie)
    assert trie.evictable_blocks() == 0      # a slot still references them
    pool.free(blocks[1])
    # tail is sole-owned but its parent is pinned: chain integrity holds,
    # the *leaf* may go while the pinned ancestor stays
    assert trie.evictable_blocks() == 1
    assert trie.evict(10) == 1
    assert trie.match(np.arange(5, dtype=np.int32)) == blocks[:1]


def test_trie_clear_returns_references():
    trie, pool = _trie(bs=4)
    toks = np.arange(8, dtype=np.int32)
    blocks = [pool.alloc(), pool.alloc()]
    trie.insert(toks, blocks)
    for blk in blocks:
        pool.free(blk)                       # request-side refs gone
    trie.clear()
    assert pool.free_blocks() == pool.stats.total_blocks
    assert trie.match(np.arange(9, dtype=np.int32)) == []


# ---------------------------------------------------------------------------
# bit-exactness: paged == contiguous through shuffled block tables


def _check_paged_matches_contiguous(cfg, model, params, steps, block_size,
                                    plens, n_decode, seed=0,
                                    max_seq=MAX_SEQ):
    """Prefill ``plens`` prompts into slots of a contiguous cache and into a
    paged pool through *shuffled* block tables, then decode ``n_decode``
    lock-steps: logits, tokens, and full cache contents must be bit-equal at
    every step."""
    prefill_c, prefill_p, decode_c, decode_p = steps
    n_slot_blocks = max_seq // block_size
    n_slots = len(plens)
    pool_n = n_slots * n_slot_blocks + 1
    rng = np.random.default_rng(seed)
    # shuffled physical layout: logical adjacency != physical adjacency
    perm = rng.permutation(np.arange(1, pool_n))
    tables = perm.reshape(n_slots, n_slot_blocks).astype(np.int32)
    cont = model.init_cache(n_slots, max_seq)
    pool = model.init_cache(pool_n, block_size)
    last = np.zeros((n_slots, 1), np.int32)
    for i, n in enumerate(plens):
        prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        tc, lc, cont = prefill_c(params, jnp.asarray(prompt[None]),
                                 jnp.int32(i), cont)
        tp, lp, pool = prefill_p(params, jnp.asarray(prompt[None]),
                                 jnp.int32(0), jnp.asarray(tables[i]), pool)
        assert np.array_equal(np.asarray(lc), np.asarray(lp)), \
            f"bs={block_size} len={n}: paged prefill logits differ"
        assert int(np.asarray(tc)[0, 0]) == int(np.asarray(tp)[0, 0])
        last[i, 0] = int(np.asarray(tc)[0, 0])
    pos = np.asarray(plens, np.int32)
    jt = jnp.asarray(tables)
    for step in range(n_decode):
        tc, lc, cont = decode_c(params, jnp.asarray(last),
                                jnp.asarray(pos), cont)
        tp, lp, pool = decode_p(params, jnp.asarray(last),
                                jnp.asarray(pos), jt, pool)
        assert np.array_equal(np.asarray(lc), np.asarray(lp)), \
            f"bs={block_size} step={step}: paged decode logits differ"
        assert np.array_equal(np.asarray(tc), np.asarray(tp))
        last = np.asarray(tc)
        pos = pos + 1
    # the gathered logical view must equal the contiguous cache bit-for-bit
    view = paged_gather(pool, jt)
    for xa, xb in zip(jax.tree.leaves(cont), jax.tree.leaves(view)):
        assert np.array_equal(np.asarray(xa), np.asarray(xb)), \
            f"bs={block_size}: paged cache contents differ from contiguous"


@pytest.mark.parametrize("block_size", [4, 8, 16])
def test_paged_matches_contiguous(dense, paged_steps, block_size):
    cfg, model, params = dense
    _check_paged_matches_contiguous(cfg, model, params, paged_steps,
                                    block_size, plens=(5, 13), n_decode=6,
                                    seed=block_size)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(block_size=st.sampled_from([4, 8, 16]),
           plen=st.integers(1, MAX_SEQ - 7),
           seed=st.integers(0, 900))
    def test_paged_matches_contiguous_property(dense, paged_steps,
                                               block_size, plen, seed):
        """Property form: any (block size, prompt length, content seed) is
        bit-exact through the paged indirection, including decode across
        block boundaries."""
        cfg, model, params = dense
        _check_paged_matches_contiguous(cfg, model, params, paged_steps,
                                        block_size, plens=(plen,),
                                        n_decode=5, seed=seed)


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "internvl2-76b"])
def test_paged_matches_contiguous_other_families(arch):
    """The paged decode stack of the other paged families (an unrolled
    dense first layer before MoE layers; a VLM backbone) is bit-exact
    against their contiguous decode too."""
    cfg = configs.get_smoke(arch)
    model = build_model(cfg, q_block=8)
    params, _ = model.init(jax.random.key(0))
    steps = (jax.jit(make_slot_prefill(model)),
             jax.jit(make_paged_slot_prefill(model)),
             jax.jit(make_decode_step(model)),
             jax.jit(make_paged_decode_step(model)))
    _check_paged_matches_contiguous(cfg, model, params, steps, 8,
                                    plens=(5, 13), n_decode=4, seed=3)


def test_paged_matches_contiguous_seeded(dense, paged_steps):
    """Deterministic sweep covering block-boundary edges (runs even without
    hypothesis): lengths on, just under, and just over block edges."""
    cfg, model, params = dense
    for bs, plen in [(4, 3), (4, 4), (4, 5), (8, 7), (8, 8), (8, 9),
                     (16, 15), (16, 16), (16, 17), (8, 1), (8, 25)]:
        _check_paged_matches_contiguous(cfg, model, params, paged_steps, bs,
                                        plens=(plen,), n_decode=4,
                                        seed=bs * 100 + plen)


# ---------------------------------------------------------------------------
# engine equivalence: paged engine == contiguous engine, token for token


def test_engine_paged_matches_contiguous(dense):
    cfg, model, params = dense
    assert serving_caps(model.cfg).paged_kv
    a, b = _mk_reqs(cfg, 4, seed=11), _mk_reqs(cfg, 4, seed=11)
    ea = ContinuousEngine(model, params, batch_size=2, max_seq=48,
                          telemetry=False)                    # paged (auto)
    eb = ContinuousEngine(model, params, batch_size=2, max_seq=48,
                          telemetry=False, kv_block_size="off")
    sa, sb = ea.serve(a), eb.serve(b)
    assert ea.block_size == 16 and eb.block_size is None
    for ra, rb in zip(a, b):
        assert ra.output == rb.output
    assert sa["tokens_decoded"] == sb["tokens_decoded"]
    assert sa["kv_pages"]["cow_copies"] == 0    # full-block-only sharing


def test_engine_explicit_block_size_matches(dense):
    cfg, model, params = dense
    a, b = _mk_reqs(cfg, 3, plen=11, seed=5), _mk_reqs(cfg, 3, plen=11, seed=5)
    ContinuousEngine(model, params, batch_size=3, max_seq=MAX_SEQ,
                     telemetry=False, kv_block_size=4).serve(a)
    ContinuousEngine(model, params, batch_size=3, max_seq=MAX_SEQ,
                     telemetry=False, kv_block_size="off").serve(b)
    for ra, rb in zip(a, b):
        assert ra.output == rb.output


# ---------------------------------------------------------------------------
# prefix cache: hit == cold, isolation, accounting


def _shared_prefix_reqs(cfg, n, shared_len=36, tail_len=6, max_new=5,
                        seed=7):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, shared_len).astype(np.int32)
    out = []
    for i in range(n):
        tail = np.random.default_rng(1000 + i).integers(
            0, cfg.vocab_size, tail_len).astype(np.int32)
        out.append(Request(i, np.concatenate([shared, tail]),
                           max_new_tokens=max_new))
    return out


def test_prefix_hit_matches_cold(dense):
    """A request served off matched prefix blocks (zero prefill compute for
    the shared span) must emit exactly the tokens a cold prefill emits."""
    cfg, model, params = dense
    warm = _shared_prefix_reqs(cfg, 4)
    cold = _shared_prefix_reqs(cfg, 4)
    ew = ContinuousEngine(model, params, batch_size=2, max_seq=64,
                          telemetry=False)
    sw = ew.serve(warm)
    ContinuousEngine(model, params, batch_size=2, max_seq=64,
                     telemetry=False, prefix_cache=False).serve(cold)
    for rw, rc in zip(warm, cold):
        assert rw.output == rc.output
    pc = sw["prefix_cache"]
    assert pc["hits"] == 3 and pc["misses"] == 1
    assert pc["hit_rate"] == pytest.approx(0.75)
    assert pc["cached_tokens"] == 3 * 32          # one 32-block per hit
    assert [r.cached_prompt_tokens for r in warm] == [0, 32, 32, 32]
    # computed tokens = total prompt - cached span
    assert sw["prefill_tokens_computed"] == \
        sw["prompt_tokens"] - pc["cached_tokens"]


def test_prefix_sharing_isolation(dense):
    """Slots decoding concurrently off the same shared prefix blocks must
    not disturb each other: same outputs as serving each request alone."""
    cfg, model, params = dense
    together = _shared_prefix_reqs(cfg, 3, max_new=6, seed=21)
    eng = ContinuousEngine(model, params, batch_size=3, max_seq=64,
                           telemetry=False)
    eng.serve(together)                      # all three share prefix blocks live
    for i in range(3):
        alone = _shared_prefix_reqs(cfg, 3, max_new=6, seed=21)[i]
        solo = ContinuousEngine(model, params, batch_size=1, max_seq=64,
                                telemetry=False, prefix_cache=False)
        solo.serve([alone])
        assert together[i].output == alone.output, \
            f"req {i}: shared-prefix decode corrupted a neighbor"


def test_prefix_cache_survives_slot_recycling(dense):
    """Trie-held blocks outlive the request that computed them: a later
    request hits the prefix after the original slot was recycled."""
    cfg, model, params = dense
    reqs = _shared_prefix_reqs(cfg, 4, seed=9)
    eng = ContinuousEngine(model, params, batch_size=1, max_seq=64,
                           telemetry=False)       # strictly sequential slots
    stats = eng.serve(reqs)
    assert stats["prefix_cache"]["hits"] == 3
    assert stats["slots_recycled"] == 4


def test_telemetry_event_carries_cached_tokens(dense):
    cfg, model, params = dense
    eng = ContinuousEngine(model, params, batch_size=2, max_seq=64)
    eng.serve(_shared_prefix_reqs(cfg, 3, seed=13))
    cached = [e.get("cached_tokens") for e in eng.tel.events
              if e["phase"] == "prefill" and "cached_tokens" in e]
    assert cached == [32, 32]                 # hits 2 and 3; miss has no key


# ---------------------------------------------------------------------------
# capacity finish (the advance-clamp fix)


@pytest.mark.parametrize("kv_block_size", ["auto", "off"])
def test_finish_at_capacity_not_clamp(dense, kv_block_size):
    """A budget beyond the cache finishes at capacity with every position
    written once — the old clamp silently rewrote max_seq-1 forever."""
    cfg, model, params = dense
    req = _mk_reqs(cfg, 1, plen=8, max_new=1000, seed=2)[0]
    eng = ContinuousEngine(model, params, batch_size=1, max_seq=MAX_SEQ,
                           telemetry=False, kv_block_size=kv_block_size)
    stats = eng.serve([req])
    assert req.finish_reason == "capacity"
    # prefill writes [0,8); 24 decode writes fill [8,32); the token sampled
    # from the last write is emitted but never written back
    assert len(req.output) == MAX_SEQ - 8 + 1
    assert stats["completed"] == 1


def test_capacity_and_length_agree_across_paths(dense):
    """Same request under paged and contiguous: identical tokens up to the
    identical capacity finish."""
    cfg, model, params = dense
    a = _mk_reqs(cfg, 2, plen=9, max_new=1000, seed=4)
    b = _mk_reqs(cfg, 2, plen=9, max_new=1000, seed=4)
    ContinuousEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                     telemetry=False).serve(a)
    ContinuousEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                     telemetry=False, kv_block_size="off").serve(b)
    for ra, rb in zip(a, b):
        assert ra.finish_reason == rb.finish_reason == "capacity"
        assert ra.output == rb.output


def test_submit_rejects_full_prompt(dense):
    cfg, model, params = dense
    eng = ContinuousEngine(model, params, batch_size=1, max_seq=MAX_SEQ,
                           telemetry=False)
    with pytest.raises(ValueError, match="decode position"):
        eng.submit(_mk_reqs(cfg, 1, plen=MAX_SEQ, seed=0)[0])
    eng.submit(_mk_reqs(cfg, 1, plen=MAX_SEQ - 1, max_new=50, seed=0)[0])


# ---------------------------------------------------------------------------
# page-aware admission + eviction under pressure


def test_admission_defers_on_page_budget(dense):
    """A pool sized for one slot's worth of blocks serializes admission
    (defer, not shed) even though two hardware slots are free."""
    cfg, model, params = dense
    reqs = _mk_reqs(cfg, 3, plen=17, max_new=10, seed=6)
    eng = ContinuousEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                           telemetry=False, kv_block_size=16,
                           kv_pool_blocks=3)      # 2 usable blocks + null
    stats = eng.serve(reqs)
    assert stats["completed"] == 3 and stats["shed"] == 0
    assert stats["peak_active"] == 1              # pages, not slots, bound it
    for r in reqs:
        assert r.finish_reason == "length" and len(r.output) == 10
    assert stats["kv_pages"]["peak_used"] <= 2


def test_trie_eviction_under_pool_pressure(dense):
    """Distinct prompts through a tight pool force LRU trie eviction; every
    request still completes and the pool never leaks blocks."""
    cfg, model, params = dense
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 17).astype(np.int32),
                    max_new_tokens=4) for i in range(5)]
    eng = ContinuousEngine(model, params, batch_size=1, max_seq=MAX_SEQ,
                           telemetry=False, kv_block_size=16,
                           kv_pool_blocks=3)
    stats = eng.serve(reqs)
    assert stats["completed"] == 5
    assert stats["prefix_cache"]["evictions"] > 0
    # all slots released + trie evicted down: no block leaked
    used = stats["kv_pages"]["total_blocks"] - stats["kv_pages"]["free_blocks"]
    assert used == len(eng.prefix) == eng.pages.used_blocks()


def test_pool_reuse_is_scrubbed(dense):
    """Recycled blocks must be zero — sequential requests through a minimal
    pool match the contiguous engine exactly (stale KV would diverge)."""
    cfg, model, params = dense
    a = _mk_reqs(cfg, 4, plen=13, max_new=5, seed=8)
    b = _mk_reqs(cfg, 4, plen=13, max_new=5, seed=8)
    ContinuousEngine(model, params, batch_size=1, max_seq=MAX_SEQ,
                     telemetry=False, kv_block_size=4, prefix_cache=False,
                     kv_pool_blocks=9).serve(a)
    ContinuousEngine(model, params, batch_size=1, max_seq=MAX_SEQ,
                     telemetry=False, kv_block_size="off").serve(b)
    for ra, rb in zip(a, b):
        assert ra.output == rb.output


# ---------------------------------------------------------------------------
# compile budget: indirection must not retrace


def test_paged_compiles_stay_bucket_bounded(dense):
    """Distinct prompt/tail lengths + block-table remaps across slot
    recycling compile at most len(buckets) prefill executables and ONE
    decode executable — same budget as the unpaged bucketed engine."""
    cfg, model, params = dense
    rng = np.random.default_rng(14)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(2, 30))).astype(np.int32),
                    max_new_tokens=3) for i in range(8)]
    eng = ContinuousEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                           telemetry=False)
    stats = eng.serve(reqs)
    assert stats["kv_block_size"] == 32
    assert stats["prefill_compiles"] <= len(eng.buckets)
    assert stats["decode_compiles"] == 1


# ---------------------------------------------------------------------------
# queue pricing net of expected cache hits


def test_queued_tokens_discounts_cached_span():
    q = RequestQueue()
    q.push(Request(0, np.arange(40, dtype=np.int32), max_new_tokens=7))
    q.push(Request(1, np.arange(10, dtype=np.int32), max_new_tokens=2))
    assert q.queued_tokens() == (40 + 7) + (10 + 2)
    cached = {0: 32, 1: 0}
    assert q.queued_tokens(lambda r: cached[r.req_id]) == (8 + 7) + (10 + 2)
    # a probe reporting more than the prompt never goes negative
    assert q.queued_tokens(lambda r: 100) == 7 + 2


def test_shed_estimate_prices_net_of_cache(dense):
    """TTL pricing sees the *uncached* prompt span: after warming the trie,
    the prefill work a queued request puts ahead of its successors is its
    tail only, not the whole prompt."""
    cfg, model, params = dense
    eng = ContinuousEngine(model, params, batch_size=1, max_seq=64,
                           telemetry=False)
    eng.serve(_shared_prefix_reqs(cfg, 1, seed=17))     # warm the trie
    warm = _shared_prefix_reqs(cfg, 2, seed=17)         # 42-token prompts
    assert eng.adapter.expected_cached(warm[0]) == 32          # one 32-block cached
    seen = []
    def spy(req, ahead, ahead_prefill=0):
        seen.append(ahead_prefill)
        return False
    eng.admission.should_shed = spy
    for r in warm:
        eng.queue.push(r)
    eng._shed_stale()
    # req0 has nothing ahead; req1 sees req0's 10-token uncached tail, not
    # its gross 42-token prompt
    assert seen == [0, len(warm[0].prompt) - 32]


# ---------------------------------------------------------------------------
# decode writes the pool in place: the touched position of each slot's block


def test_decode_writes_only_touched_blocks(dense):
    """One decode step changes, in every layer, each active slot's write
    position in its own block and nothing else; a free slot (table on the
    null block, position 0) writes only the null block. Remapped tables and
    positions reuse the one donated-pool executable."""
    from repro.core.tracing import TraceStats, counting_jit
    cfg, model, params = dense
    bs, nb = 4, MAX_SEQ // 4
    rng = np.random.default_rng(21)
    n_pool = 2 * nb + 1
    pool = tuple(jnp.asarray(rng.normal(size=c.shape), c.dtype)
                 for c in model.init_cache(n_pool, bs))
    tables = np.zeros((3, nb), np.int32)                 # slot 2: free
    tables[:2] = rng.permutation(np.arange(1, n_pool)).reshape(2, nb)
    stats = TraceStats()
    step = counting_jit(make_paged_decode_step(model), "decode", stats,
                        donate_argnums=(4,))
    tokens = jnp.asarray([[3], [7], [0]], jnp.int32)
    for pos in ([5, 13, 0], [6, 31, 0]):
        before = [np.asarray(c, np.float32) for c in pool]
        _, _, new = step(params, tokens, jnp.asarray(pos, jnp.int32),
                         jnp.asarray(tables), pool)
        assert all(c.is_deleted() for c in pool)         # donated
        pool = new
        touched = {(tables[s, p // bs], p % bs) for s, p in enumerate(pos)}
        assert (PagePool.NULL, 0) in touched
        for b4, c in zip(before, pool):
            changed = np.argwhere((np.asarray(c, np.float32) != b4)
                                  .any(axis=(3, 4)))     # [layer, blk, off]
            got = {(int(blk), int(off)) for _, blk, off in changed}
            assert got <= touched
            for s in (0, 1):                             # every layer wrote
                blk, off = tables[s, pos[s] // bs], pos[s] % bs
                assert {int(li) for li, b, o in changed
                        if (b, o) == (blk, off)} == set(range(cfg.num_layers))
        tables[0] = np.roll(tables[0], 1)                # a remap
    assert stats.compiles("decode") == 1


def test_decode_reports_its_attention_and_kv_tokens(dense):
    """Run stats name the decode attention as lowered (the reference off
    a TPU), and each ``decode_step`` span counts the cache positions the
    attention read: every slot's [0, pos]."""
    cfg, model, params = dense
    eng = ContinuousEngine(model, params, batch_size=2, max_seq=MAX_SEQ,
                           telemetry=False)
    stats = eng.serve(_mk_reqs(cfg, 2, plen=5, max_new=4))
    assert stats["decode_attention"] == "reference"
    kv = [s.attrs["kv_tokens"] for s in eng.tracer.spans()
          if s.name == "decode_step"]
    assert kv == [12, 14, 16]        # two slots from position 5, 3 steps
