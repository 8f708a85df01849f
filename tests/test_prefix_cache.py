"""Shared-prefix paged serving: refcount/COW edge cases, prefix-index
behavior, chunked prefill-into-pages parity, and the three-way batcher
equality (dense == paged == prefix-shared, EXACT at f32).

The allocator invariants: a page returns to the free list only when its
LAST reference drops (double release is an error, not a silent corruption),
COW privatizes with exactly one copy and one decrement, and prefix eviction
never frees a page a live slot still shares."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime.batcher import ContinuousBatcher, Request
from repro.runtime.kv_pages import PagePool
from repro.runtime.prefix_cache import PrefixIndex


# ---------------------------------------------------------------------------
# refcount / COW unit tests (pure host-side)
# ---------------------------------------------------------------------------


def test_decref_double_release_is_error():
    pool = PagePool(num_pages=4, page_size=4)
    [page] = pool.reserve(0, 4)
    assert pool.refcount(page) == 1
    assert pool.decref(page) == 0  # frees
    with pytest.raises(ValueError, match="double release"):
        pool.decref(page)
    # incref of a free page is equally an error: nothing to share
    with pytest.raises(ValueError, match="not allocated"):
        pool.incref(page)


def test_release_decrements_instead_of_frees():
    pool = PagePool(num_pages=4, page_size=4)
    pages = pool.reserve(0, 8)
    pool.try_reserve(1, 8, shared=pages)  # slot 1 shares both pages
    assert [pool.refcount(p) for p in pages] == [2, 2]
    assert pool.release(0) == 0  # nothing actually freed: slot 1 remains
    assert pool.pages_in_use == 2
    assert [pool.refcount(p) for p in pages] == [1, 1]
    assert pool.release(1) == 2  # last reference: pages return to the pool
    assert pool.pages_in_use == 0


def test_cow_three_way_copies_once_and_decrements_once():
    pool = PagePool(num_pages=8, page_size=4)
    [page] = pool.reserve(0, 4)
    pool.try_reserve(1, 4, shared=[page])
    pool.try_reserve(2, 4, shared=[page])
    assert pool.refcount(page) == 3  # shared 3 ways
    free_before = pool.pages_free
    old, new = pool.cow(1, 0)
    assert old == page and new != page          # one fresh copy...
    assert pool.pages_free == free_before - 1   # ...costing one page
    assert pool.refcount(page) == 2             # decremented exactly once
    assert pool.refcount(new) == 1
    assert pool.owned(1) == [new]
    assert pool.owned(0) == [page] and pool.owned(2) == [page]
    # a page held exclusively needs no copy: cow is the identity
    assert pool.cow(1, 0) == (new, new)
    assert pool.pages_free == free_before - 1


def test_cow_exhausted_pool_returns_none_unchanged():
    pool = PagePool(num_pages=2, page_size=4)
    [page] = pool.reserve(0, 4)
    pool.try_reserve(1, 4, shared=[page])
    pool.reserve(2, 4)  # burn the last free page
    assert pool.cow(1, 0) is None
    assert pool.refcount(page) == 2 and pool.owned(1) == [page]


def test_shared_reservation_counts_and_stats():
    pool = PagePool(num_pages=8, page_size=4)
    pages = pool.reserve(0, 12)  # 3 pages
    got = pool.try_reserve(1, 14, shared=pages[:2])  # 2 shared + 2 fresh
    assert got is not None and got[:2] == pages[:2]
    assert pool.pages_in_use == 5  # 3 + 2 fresh: shared pages not re-counted
    st = pool.stats()
    assert st.pages_shared == 2 and st.shared_high_water >= 2


# ---------------------------------------------------------------------------
# prefix index
# ---------------------------------------------------------------------------


def test_index_insert_lookup_full_and_partial():
    pool = PagePool(num_pages=16, page_size=4)
    idx = PrefixIndex(pool)
    pages = pool.reserve(0, 12)
    prompt = list(range(100, 112))  # 3 full pages
    assert idx.insert(prompt, pages) == 3
    assert [pool.refcount(p) for p in pages] == [2, 2, 2]  # index pins

    # full-page hit, capped at floor((len-1)/ps): an identical prompt
    # matches 2 full pages + a partial (the last token must still decode)
    hit = idx.lookup(prompt)
    assert hit.pages == pages[:2]
    assert (hit.partial_page, hit.partial_tokens) == (pages[2], 3)
    assert hit.matched_tokens == 11

    # divergence inside page 2: full pages 0-1 shared, page 2 partial
    hit = idx.lookup(prompt[:10] + [777, 776])
    assert hit.pages == pages[:2]
    assert (hit.partial_page, hit.partial_tokens) == (pages[2], 2)

    # divergence at a page boundary: clean full-page match, no partial
    hit = idx.lookup(prompt[:8] + [777, 776, 775, 774])
    assert hit.pages == pages[:2] and hit.partial_tokens == 0

    # miss at the first page
    hit = idx.lookup([1, 2, 3, 4, 5])
    assert hit.pages == [] and hit.matched_tokens == 0

    # re-inserting the same prompt adds nothing and pins nothing twice
    assert idx.insert(prompt, pages) == 0
    assert [pool.refcount(p) for p in pages] == [2, 2, 2]


def test_prefix_eviction_never_frees_pinned_page():
    pool = PagePool(num_pages=8, page_size=4)
    idx = PrefixIndex(pool)
    pages = pool.reserve(0, 12)
    prompt = list(range(200, 212))
    idx.insert(prompt, pages)
    # slot 1 mounts the first page shared (a live request using the prefix)
    pool.try_reserve(1, 4, shared=[pages[0]])
    pool.release(0)  # original owner gone; index pins all 3, slot 1 shares 1
    assert pool.refcount(pages[0]) == 2  # pinned: index + slot 1
    freed = idx.evict(100)
    # the leaf chain (pages 2 then 1) evicts; the pinned root page survives
    assert freed == 2
    assert pool.refcount(pages[0]) == 2
    assert pool.refcount(pages[1]) == 0 and pool.refcount(pages[2]) == 0
    assert idx.entries == 1
    # once the sharing slot releases, the page becomes evictable
    pool.release(1)
    assert idx.evict(100) == 1
    assert idx.entries == 0 and pool.pages_in_use == 0


def test_prefix_eviction_is_lru():
    pool = PagePool(num_pages=8, page_size=2)
    idx = PrefixIndex(pool)
    a = pool.reserve(0, 2)
    idx.insert([1, 2], a)
    b = pool.reserve(1, 2)
    idx.insert([3, 4], b)
    pool.release(0)
    pool.release(1)
    idx.lookup([1, 2, 9])  # touch chain A: B becomes least recently used
    assert idx.evict(1) == 1
    assert pool.refcount(a[0]) == 1  # A survived
    assert pool.refcount(b[0]) == 0  # B evicted


def test_lookup_peek_does_not_touch_lru():
    """peek=True lookups are read-only: they must not renew recency, so
    the hit-aware admission scan (which peeks every queued candidate)
    cannot turn the whole queue's prefixes 'recently used' and break LRU
    eviction."""
    pool = PagePool(num_pages=8, page_size=2)
    idx = PrefixIndex(pool)
    a = pool.reserve(0, 2)
    idx.insert([1, 2], a)
    b = pool.reserve(1, 2)
    idx.insert([3, 4], b)
    pool.release(0)
    pool.release(1)
    # peek chain A repeatedly: B stays the most recently used (insert order)
    for _ in range(3):
        hit = idx.lookup([1, 2, 9], peek=True)
        assert hit.pages == a  # same result as a real lookup...
    assert idx.evict(1) == 1
    assert pool.refcount(a[0]) == 0  # ...but A still evicts first (LRU)
    assert pool.refcount(b[0]) == 1


# ---------------------------------------------------------------------------
# chunked prefill-into-pages parity (model level)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("llama3.2-1b-smoke")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.mark.parametrize("chunk", [1, 3, 8])
def test_prefill_into_pages_matches_token_stepping(model_and_params, chunk):
    """prefill_step_paged over [0, L) in chunks must leave the SAME pages
    and produce the same next-token logits as L decode_step_paged calls."""
    cfg, model, params = model_and_params
    ps, L = 4, 8
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, L).astype(np.int32)

    def run(prefill_chunk):
        pool = PagePool(num_pages=8, page_size=ps)
        pool.reserve(0, L + 1)
        table = jnp.asarray(pool.page_table(1, 4))
        cache = model.make_paged_cache(pool.total_pages, ps, mode="init",
                                       dtype=jnp.float32)
        logits = None
        if prefill_chunk:
            t = 0
            while t < L:
                c = min(prefill_chunk, L - t)
                logits, cache = model.prefill_step_paged(
                    params, jnp.asarray(prompt[t:t + c][None, :]), cache,
                    jnp.asarray([t], np.int32), table)
                t += c
            logits = logits[:, -1]  # last chunk's last position
        else:
            for t in range(L):
                lengths = jnp.asarray([t + 1], np.int32)
                logits, cache = model.decode_step_paged(
                    params, jnp.asarray(prompt[t:t + 1][None, :]), cache,
                    jnp.asarray([t], np.int32), table, lengths)
            logits = logits[:, -1]
        return np.asarray(logits), cache

    want_logits, want_cache = run(0)
    got_logits, got_cache = run(chunk)
    np.testing.assert_allclose(got_logits, want_logits, atol=2e-5, rtol=2e-5)
    for seg in want_cache:
        for leaf in want_cache[seg]:
            np.testing.assert_allclose(
                np.asarray(got_cache[seg][leaf]),
                np.asarray(want_cache[seg][leaf]), atol=2e-5, rtol=2e-5,
                err_msg=f"{seg}/{leaf}")


def test_prefill_into_pages_int8_quantize_on_write(model_and_params):
    """int8 cache: the chunked prefill path must write the same quantized
    payloads + scale pages as the token-by-token decode path."""
    from repro.core.precision import QuantSpec

    cfg, model, params = model_and_params
    ps, L = 4, 8
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, L).astype(np.int32)
    kv_quant = QuantSpec("int8", "tile")

    def run(chunked):
        pool = PagePool(num_pages=8, page_size=ps)
        pool.reserve(0, L + 1)
        table = jnp.asarray(pool.page_table(1, 4))
        cache = model.make_paged_cache(pool.total_pages, ps, mode="init",
                                       dtype=jnp.float32, kv_quant=kv_quant)
        if chunked:
            _, cache = model.prefill_step_paged(
                params, jnp.asarray(prompt[None, :]), cache,
                jnp.asarray([0], np.int32), table)
        else:
            for t in range(L):
                _, cache = model.decode_step_paged(
                    params, jnp.asarray(prompt[t:t + 1][None, :]), cache,
                    jnp.asarray([t], np.int32), table,
                    jnp.asarray([t + 1], np.int32))
        return cache

    want, got = run(False), run(True)
    for seg in want:
        assert str(got[seg]["k_pages"].dtype) == "int8"
        for leaf in want[seg]:
            np.testing.assert_allclose(
                np.asarray(got[seg][leaf]).astype(np.float32),
                np.asarray(want[seg][leaf]).astype(np.float32),
                atol=1e-5, rtol=1e-5, err_msg=f"{seg}/{leaf}")


# ---------------------------------------------------------------------------
# batcher integration: the acceptance scenario
# ---------------------------------------------------------------------------


def _shared_prefix_requests(cfg, *, plen=16, frac=0.75, n=2, max_new=4,
                            seed=0):
    """n requests whose first frac*plen tokens are identical."""
    rng = np.random.default_rng(seed)
    common = rng.integers(0, cfg.vocab, int(plen * frac))
    out = []
    for i in range(n):
        tail = rng.integers(0, cfg.vocab, plen - len(common))
        out.append(Request(
            rid=i, prompt=np.concatenate([common, tail]).astype(np.int32),
            max_new=max_new))
    return out


@pytest.mark.slow
def test_prefix_admission_reserves_only_tail_pages(model_and_params):
    """Two requests with a common 75%-of-prompt prefix: after the first is
    indexed, admitting the second must reserve EXACTLY the tail pages
    (total pages for its footprint minus the shared full prefix pages),
    and its decode output must equal the unshared paged run's."""
    cfg, model, params = model_and_params
    ps, plen, max_new = 4, 16, 4
    reqs = _shared_prefix_requests(cfg, plen=plen, frac=0.75, max_new=max_new)

    # unshared paged reference for request 1
    ref = ContinuousBatcher(model, params, batch_slots=1, max_len=24,
                            paged=True, page_size=ps)
    ref.submit(Request(rid=9, prompt=reqs[1].prompt, max_new=max_new))
    want = ref.run_to_completion()[9].output

    b = ContinuousBatcher(model, params, batch_slots=1, max_len=24,
                          paged=True, page_size=ps, num_pages=24,
                          prefix_cache=True, prefill_chunk=4)
    b.submit(reqs[0])
    b.run_to_completion()
    # request 0 finished: its 4 full prompt pages are pinned by the index
    in_use_before = b.pool_stats().pages_in_use
    assert in_use_before == plen // ps

    b.submit(reqs[1])
    b.step()  # admission happens here
    shared_pages = int(0.75 * plen) // ps                   # 3 full pages
    total_pages = b.pool.pages_for(plen + max_new)          # 5 pages
    in_use_after = b.pool_stats().pages_in_use
    # EXACT: only the tail pages are new
    assert in_use_after - in_use_before == total_pages - shared_pages
    st = b.prefix_stats()
    assert st["hits"] == 1 and st["tokens_saved"] == shared_pages * ps
    # the live slot reuses exactly the 3 prefix pages it did not prefill
    assert st["pages_reused"] == shared_pages
    # the slot's leading pages ARE the indexed prefix pages (lookup after
    # the stats read: it bumps the hit counters)
    assert b.pool.owned(0)[:shared_pages] == b.prefix.lookup(
        reqs[1].prompt).pages

    fin = b.run_to_completion()
    assert fin[1].output == want  # identical to the unshared paged path


@pytest.mark.slow
def test_dense_paged_prefix_outputs_exactly_equal(model_and_params):
    """The three-way acceptance check: dense rectangle, plain paged, and
    prefix-shared paged (chunked prefill + COW) produce EXACTLY the same
    outputs for a shared-prefix request stream at f32."""
    cfg, model, params = model_and_params

    # 5 requests, 75% common prefix, prompt length NOT page aligned so the
    # partial-page COW path runs too
    def reqs():
        return _shared_prefix_requests(cfg, plen=14, frac=0.75, n=5,
                                       max_new=4, seed=2)
    dense = ContinuousBatcher(model, params, batch_slots=2, max_len=20)
    for r in reqs():
        dense.submit(r)
    want = {k: v.output for k, v in dense.run_to_completion().items()}

    paged = ContinuousBatcher(model, params, batch_slots=2, max_len=20,
                              paged=True, page_size=4)
    for r in reqs():
        paged.submit(r)
    got_paged = {k: v.output for k, v in paged.run_to_completion().items()}

    pref = ContinuousBatcher(model, params, batch_slots=2, max_len=20,
                             paged=True, page_size=4, num_pages=40,
                             prefix_cache=True, prefill_chunk=4)
    for r in reqs():
        pref.submit(r)
    got_pref = {k: v.output for k, v in pref.run_to_completion().items()}

    assert got_paged == want
    assert got_pref == want
    st = pref.prefix_stats()
    assert st["hits"] >= 3          # everyone after the first two shares
    assert st["cow_copies"] >= 1    # 14 % 4 != 0: intra-page divergence
    # only index pins remain after completion (one page per entry)
    assert pref.pool_stats().pages_in_use == pref.prefix.entries


# what the five shared-prefix requests of the test below were served when
# each layer's pool was its own (P, ps, Hkv, hd) slice of the stack and no
# program donated it
_SERVED_BEFORE_DONATION = {0: [198, 198, 198, 198], 1: [33, 35, 235, 245],
                           2: [194, 194, 194, 194], 3: [32, 198, 198, 198],
                           4: [123, 198, 198, 198]}


def test_donated_programs_consume_the_cache_they_are_given(model_and_params):
    """Chunked prefill and the prefix cache's page copy donate the cache:
    each call consumes the pools it is handed, and the batcher serves on
    from the pools it gets back and never reads a consumed one (that would
    raise).  The tokens served through prefill and COW are those served
    before the pools were stacked lane-dense and donated."""
    cfg, model, params = model_and_params
    pref = ContinuousBatcher(model, params, batch_slots=2, max_len=20,
                             paged=True, page_size=4, num_pages=40,
                             prefix_cache=True, prefill_chunk=4)
    handed = {"_prefill": [], "_copy_page": []}

    def spy(name, at):
        program = getattr(pref, name)

        def call(*args):
            out = program(*args)
            handed[name].append(args[at])
            return out
        setattr(pref, name, call)

    spy("_prefill", 2)
    spy("_copy_page", 0)
    for r in _shared_prefix_requests(cfg, plen=14, frac=0.75, n=5,
                                     max_new=4, seed=2):
        pref.submit(r)
    got = {k: v.output for k, v in pref.run_to_completion().items()}
    assert got == _SERVED_BEFORE_DONATION
    assert pref.prefix_stats()["cow_copies"] == len(handed["_copy_page"]) >= 1
    assert len(handed["_prefill"]) >= 5
    for caches in handed.values():
        assert all(leaf.is_deleted() for cache in caches
                   for leaf in jax.tree.leaves(cache))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(pref.cache))


@pytest.mark.slow
def test_prefix_cache_under_pool_pressure(model_and_params):
    """A tight pool forces index eviction during admission; everything
    still completes with outputs equal to the unconstrained paged run."""
    cfg, model, params = model_and_params

    def reqs():
        return _shared_prefix_requests(cfg, plen=12, frac=0.5, n=6,
                                       max_new=3, seed=3)
    paged = ContinuousBatcher(model, params, batch_slots=2, max_len=16,
                              paged=True, page_size=4)
    for r in reqs():
        paged.submit(r)
    want = {k: v.output for k, v in paged.run_to_completion().items()}

    tight = ContinuousBatcher(model, params, batch_slots=2, max_len=16,
                              paged=True, page_size=4, num_pages=10,
                              prefix_cache=True, prefill_chunk=4)
    for r in reqs():
        tight.submit(r)
    got = {k: v.output for k, v in tight.run_to_completion().items()}
    assert got == want
    assert tight.pool_stats().high_water <= 10


@pytest.mark.slow
def test_admission_eviction_spares_the_plan_and_frees_lru(model_and_params):
    """Admission under pool pressure evicts an older, unrelated index chain
    to make room — but never the pages of the admission's OWN prefix hit
    (evicting those would invalidate the reservation it is about to make)."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(4)
    b = ContinuousBatcher(model, params, batch_slots=1, max_len=16,
                          paged=True, page_size=4, num_pages=4,
                          prefix_cache=True, prefill_chunk=4)
    prompt_a = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    b.submit(Request(rid=0, prompt=prompt_a, max_new=4))
    b.run_to_completion()
    prompt_c = rng.integers(0, cfg.vocab, 4).astype(np.int32)
    b.submit(Request(rid=1, prompt=prompt_c, max_new=4))
    b.run_to_completion()
    assert b.prefix.entries == 3  # A's 2 full pages + C's 1
    # B hits A's two pages and needs two fresh ones; only one is free, so
    # admission must evict C's (LRU, unpinned) page — not A's hit pages
    prompt_b = np.concatenate(
        [prompt_a, rng.integers(0, cfg.vocab, 4)]).astype(np.int32)
    b.submit(Request(rid=2, prompt=prompt_b, max_new=4))
    fin = b.run_to_completion()
    assert fin[2].done
    st = b.prefix_stats()
    assert st["evicted_pages"] == 1
    assert st["hits"] >= 1 and st["tokens_saved"] >= 8


@pytest.mark.slow
def test_admission_never_evicts_its_own_hit_pages(model_and_params):
    """A pool too small for the request even WITH its prefix hit must
    back-pressure, not evict the hit's pages out from under the plan
    (which used to crash try_reserve with 'shared page not allocated')."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(5)
    b = ContinuousBatcher(model, params, batch_slots=1, max_len=16,
                          paged=True, page_size=4, num_pages=3,
                          prefix_cache=True, prefill_chunk=4)
    prompt_a = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    b.submit(Request(rid=0, prompt=prompt_a, max_new=4))
    b.run_to_completion()
    assert b.prefix.entries == 2
    # B needs 4 pages; the pool has 3.  The only evictable entries are B's
    # own hit pages — admission must skip them and back-pressure forever,
    # never raise.
    prompt_b = np.concatenate(
        [prompt_a, rng.integers(0, cfg.vocab, 4)]).astype(np.int32)
    b.submit(Request(rid=1, prompt=prompt_b, max_new=4))
    fin = b.run_to_completion(max_steps=30)
    # not crashed, not silently lost: terminated with a typed reason at
    # max_steps (the lifecycle contract replaced "absent from finished")
    assert fin[1].finish_reason == "deadline"
    assert fin[1].output == []   # never admitted, never decoded
    assert b.prefix.entries == 2  # the hit pages survived


@pytest.mark.slow
def test_chunked_prefill_overlong_prompt_truncates_not_crashes(
        model_and_params):
    """An over-long prompt through the CHUNKED paged prefill must clip to
    the slot's reservation and degrade exactly like the token-stepping
    path (truncate + evict), never write past the reserved pages."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(6)
    b = ContinuousBatcher(model, params, batch_slots=2, max_len=8,
                          paged=True, page_size=4, num_pages=8,
                          prefix_cache=True, prefill_chunk=4)
    b.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab, 12).astype(
        np.int32), max_new=2))
    b.submit(Request(rid=1, prompt=rng.integers(0, cfg.vocab, 3).astype(
        np.int32), max_new=2))
    fin = b.run_to_completion()
    assert set(fin) == {0, 1}
    assert len(fin[1].output) == 2  # the well-formed request is unaffected


@pytest.mark.slow
def test_hit_aware_admission_prefers_longest_prefix_hit(model_and_params):
    """With the index warm, admission reorders same-priority queued
    requests to take the longest resident-prefix match first — the
    cold-prompt request submitted EARLIER is admitted later, and both
    decode the same outputs as a plain FIFO paged run."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(7)
    prompt_a = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    cold = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    warm = np.concatenate(
        [prompt_a, rng.integers(0, cfg.vocab, 2)]).astype(np.int32)

    ref = ContinuousBatcher(model, params, batch_slots=1, max_len=16,
                            paged=True, page_size=4)
    for rid, p in ((1, cold), (2, warm)):
        ref.submit(Request(rid=rid, prompt=p, max_new=3))
    want = {k: v.output for k, v in ref.run_to_completion().items()}

    b = ContinuousBatcher(model, params, batch_slots=1, max_len=16,
                          paged=True, page_size=4, num_pages=16,
                          prefix_cache=True, prefill_chunk=4)
    b.submit(Request(rid=0, prompt=prompt_a, max_new=3))
    b.run_to_completion()  # A's 2 full prompt pages now indexed
    b.submit(Request(rid=1, prompt=cold, max_new=3))   # FIFO-first, no hit
    b.submit(Request(rid=2, prompt=warm, max_new=3))   # 2-page hit
    fin = b.run_to_completion()

    def admitted_at(req):
        return dict(req.events)["admitted"]

    assert admitted_at(fin[2]) < admitted_at(fin[1])  # hit jumped the line
    assert b.prefix_stats()["hits"] >= 1
    assert {k: fin[k].output for k in (1, 2)} == want  # ordering-only change


@pytest.mark.slow
def test_hit_aware_admission_never_overrides_priority(model_and_params):
    """Hit-aware ordering applies WITHIN a priority tier only: a
    higher-priority cold prompt still beats a lower-priority request with
    a full prefix hit."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(8)
    prompt_a = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    b = ContinuousBatcher(model, params, batch_slots=1, max_len=16,
                          paged=True, page_size=4, num_pages=16,
                          prefix_cache=True, prefill_chunk=4)
    b.submit(Request(rid=0, prompt=prompt_a, max_new=3))
    b.run_to_completion()
    warm = np.concatenate(
        [prompt_a, rng.integers(0, cfg.vocab, 2)]).astype(np.int32)
    cold = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    b.submit(Request(rid=1, prompt=warm, max_new=3, priority=0))
    b.submit(Request(rid=2, prompt=cold, max_new=3, priority=1))
    fin = b.run_to_completion()
    assert (dict(fin[2].events)["admitted"]
            < dict(fin[1].events)["admitted"])


def test_prefix_cache_requires_paged(model_and_params):
    cfg, model, params = model_and_params
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatcher(model, params, batch_slots=2, max_len=16,
                          prefix_cache=True)
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatcher(model, params, batch_slots=2, max_len=16,
                          prefill_chunk=4)
