"""Reader/Planner/Executor stack: batched results identical to per-query
search, cache hits free, joins exact beyond int32 packing, and all four
planner routes element-wise identical across join backends.

Query streams, the hypothesis query strategy and the element-wise
equivalence assertion live in ``tests/oracles.py`` (shared with the
multi-key and sharded suites)."""

import functools

import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, strategies as st

from repro.core.lexicon import FREQUENT, OTHER, STOP, make_lexicon
from repro.core.proximity import ProximityEngine
from repro.core.strategies import StrategyConfig
from repro.core.text_index import IndexSetConfig, TextIndexSet
from repro.data.corpus import generate_part
from repro.search import (
    ROUTE_MULTI,
    ROUTE_ORDINARY,
    ROUTE_STOPSEQ,
    ROUTE_WV,
    IndexReader,
    PostingCache,
    Query,
    SearchService,
    jax_window_join,
    numpy_window_join,
    pos_scale,
)
from tests.oracles import (
    QUERY_SPEC,
    assert_results_identical,
    class_pools,
    core_queries,
    mixed_queries,
    spec_to_query,
    words_of_class,
)

BACKENDS = ("numpy", "jax", "pallas")


@pytest.fixture(scope="module")
def small_world():
    lex = make_lexicon(
        n_words=8000, n_lemmas=3500, n_stop=30, n_frequent=200, seed=11
    )
    t1, o1 = generate_part(lex, n_docs=150, avg_doc_len=250, doc0=0, seed=1)
    t2, o2 = generate_part(lex, n_docs=150, avg_doc_len=250, doc0=150, seed=2)
    cfg = IndexSetConfig(
        strategy=StrategyConfig.set2(cluster_size=2048),
        build_ordinary_all=True,
        fl_area_clusters=128,
    )
    ts = TextIndexSet(cfg, lex, seed=0)
    ts.add_documents(t1, o1, 0)
    ts.add_documents(t2, o2, 150)
    return lex, ts


# ------------------------------------------------------------ the planner --
def test_planner_routes_and_grouping(small_world):
    lex, ts = small_world
    svc = SearchService(ts, window=3)
    qs = mixed_queries(lex, n=64)
    plan = svc.plan(qs)
    census = plan.route_census()
    assert census[ROUTE_STOPSEQ] >= 16
    assert census[ROUTE_WV] >= 8
    assert census[ROUTE_ORDINARY] >= 8
    # grouped lookups are unique and keyed by real dictionary groups
    total = sum(len(v) for v in plan.grouped.values())
    flat = {(lk.index, lk.key) for v in plan.grouped.values() for lk in v}
    assert len(flat) == total == plan.n_unique_lookups
    per_query = sum(len(pq.lookups) for pq in plan.queries)
    assert total < per_query, "batch planning must dedupe repeated keys"
    for (index, group), lks in plan.grouped.items():
        for lk in lks:
            assert lk.group == group == ts.indexes[index].dict.group_of(lk.key)


def test_query_validation():
    with pytest.raises(ValueError):
        Query((1,))
    with pytest.raises(ValueError):
        Query((1, 2, 3, 4))


# ----------------------------------------------- batched == per-query loop --
@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_batched_identical_to_per_query(small_world, backend):
    lex, ts = small_world
    eng = ProximityEngine(ts, window=3)
    svc = SearchService(ts, window=3, backend=backend)
    qs = mixed_queries(lex, n=64)
    batch = svc.search_batch(qs)
    assert len(batch) == 64
    routes = set()
    for q, r in zip(qs, batch):
        ref = eng.search(q)
        routes.add(r.route)
        assert_results_identical(ref, r, ctx=(backend, q))
    assert routes == {ROUTE_STOPSEQ, ROUTE_WV, ROUTE_ORDINARY}


def test_batched_agrees_with_ordinary_baseline(small_world):
    lex, ts = small_world
    eng = ProximityEngine(ts, window=3)
    svc = SearchService(ts, window=3, backend="jax")
    qs = mixed_queries(lex, n=16)
    for q, r in zip(qs, svc.search_batch(qs)):
        rb = eng.search_ordinary(q)
        assert set(r.docs.tolist()) == set(rb.docs.tolist()), q


# ------------------------------------------------------- reader I/O + LRU --
def test_cache_hits_charge_zero_io(small_world):
    lex, ts = small_world
    svc = SearchService(ts, window=3)
    qs = mixed_queries(lex, n=32)
    svc.search_batch(qs)
    warm = {n: s.total_ops for n, s in ts.search_io().items()}
    stats0 = svc.reader.cache_stats
    h0, b0 = stats0.hits, stats0.bytes_used
    svc.search_batch(qs)  # every lookup now a cache hit
    after = {n: s.total_ops for n, s in ts.search_io().items()}
    assert warm == after, "cache hits must charge zero search-device I/O"
    assert svc.reader.cache_stats.hits > h0
    assert svc.reader.cache_stats.bytes_used == b0


def test_reader_refreshes_after_writer_update(small_world):
    lex, _ = small_world
    cfg = IndexSetConfig(strategy=StrategyConfig.set1(cluster_size=2048))
    ts = TextIndexSet(cfg, lex, seed=0)
    t1, o1 = generate_part(lex, n_docs=60, avg_doc_len=200, doc0=0, seed=21)
    t2, o2 = generate_part(lex, n_docs=60, avg_doc_len=200, doc0=60, seed=22)
    ts.add_documents(t1, o1, 0)
    reader = ts.reader()
    key = next(iter(ts.indexes["known"].dict.entries))
    before = reader.lookup("known", key).copy()
    ts.add_documents(t2, o2, 60)  # writer advances: cached postings stale
    after = reader.lookup("known", key)
    fresh = ts.indexes["known"].lookup(key)
    assert np.array_equal(after, fresh)
    assert after.shape[0] >= before.shape[0]


def test_per_query_window_clamped_to_max_distance(small_world):
    """A Query window beyond cfg.max_distance must clamp: the stopseq/wv
    indexes are precomputed at max_distance, so a wider ordinary join
    would give route-dependent proximity semantics."""
    lex, ts = small_world
    svc = SearchService(ts, window=3)
    other = words_of_class(lex, OTHER)
    q = [other[1], other[2]]
    wide = svc.search_batch([Query(tuple(q), window=50)])[0]
    default = svc.search_batch([q])[0]
    assert np.array_equal(wide.docs, default.docs)
    assert np.array_equal(wide.witnesses, default.witnesses)


def test_refresh_noop_preserves_cache(small_world):
    """Regression: refresh() used to drop every cached posting even when
    the writer's generation was unchanged, turning periodic refresh
    sweeps into full cache cold-starts.  A no-op refresh must keep cache
    hits alive and charge zero new device I/O."""
    lex, ts = small_world
    reader = ts.reader()
    key = next(iter(ts.indexes["known"].dict.entries))
    first = reader.lookup("known", key)
    io0 = {n: s.total_ops for n, s in reader.io_stats().items()}
    reader.refresh()  # no writer advance: must be a no-op
    assert len(reader.cache) > 0
    assert reader.cache.stats.invalidations == 0
    h0 = reader.cache.stats.hits
    again = reader.lookup("known", key)
    assert np.array_equal(again, first)
    assert not again.flags.writeable  # served from the immutable cache slot
    assert reader.cache.stats.hits == h0 + 1
    assert {n: s.total_ops for n, s in reader.io_stats().items()} == io0


def test_drop_index_counts_invalidations_and_reclaims_floor():
    """Regression: drop_index used to shrink the cache silently — no
    stats trace — which skewed eviction-rate dashboards.  Invalidations
    are counted separately from capacity evictions, and every dropped
    entry reclaims the same MIN_CHARGE-floored charge it was admitted
    at (bytes_used returns exactly to zero, even for floor-charged
    negative-cache entries)."""
    cache = PostingCache(budget_bytes=1 << 16)
    empty = np.zeros((0, 2), np.int64)      # floor-charged entries
    small = np.zeros((4, 2), np.int64)      # real-charge entries
    for k in range(3):
        cache.put("a", k, empty)
        cache.put("b", k, small)
    assert cache.stats.bytes_used == 3 * cache.MIN_CHARGE + 3 * small.nbytes
    cache.drop_index("a")
    assert cache.stats.invalidations == 3
    assert cache.stats.evictions == 0, "drops are not capacity evictions"
    assert cache.stats.bytes_used == 3 * small.nbytes
    assert len(cache) == 3
    cache.drop_index("b")
    assert cache.stats.invalidations == 6
    assert cache.stats.bytes_used == 0
    assert len(cache) == 0


def test_negative_cache_entries_stay_bounded():
    cache = PostingCache(budget_bytes=PostingCache.MIN_CHARGE * 8)
    empty = np.zeros((0, 2), np.int64)
    for k in range(100):  # a stream of distinct absent keys
        cache.put("i", k, empty)
    assert len(cache) <= 8, "zero-byte entries must respect the budget"
    assert cache.stats.evictions > 0


def test_cache_budget_evicts():
    cache = PostingCache(budget_bytes=1024)
    a = np.zeros((32, 2), np.int64)  # 512 B each
    cache.put("i", 1, a)
    cache.put("i", 2, a)
    cache.put("i", 3, a)  # evicts key 1 (LRU)
    assert cache.get("i", 1) is None
    assert cache.get("i", 3) is not None
    assert cache.stats.bytes_used <= 1024
    assert cache.stats.evictions == 1
    # oversized values are passed through, never cached
    cache.put("i", 4, np.zeros((200, 2), np.int64))
    assert cache.get("i", 4) is None


def test_cache_keys_namespaced_by_index():
    """Regression: a numerically equal packed key in two different
    indexes (e.g. an extended (w, v) key and a 2-word multi key) must
    occupy distinct cache slots and never answer for each other."""
    cache = PostingCache(budget_bytes=1 << 16)
    key = (7 << 32) | 42  # same integer under both index names
    wv = np.asarray([[1, 2]], np.int64)
    multi = np.asarray([[3, 4], [5, 6]], np.int64)
    cache.put("wv_kk", key, wv)
    cache.put("multi", key, multi)
    assert np.array_equal(cache.get("wv_kk", key), wv)
    assert np.array_equal(cache.get("multi", key), multi)
    cache.drop_index("wv_kk")
    assert cache.get("wv_kk", key) is None
    assert np.array_equal(cache.get("multi", key), multi)


def test_cached_postings_are_readonly(small_world):
    lex, ts = small_world
    svc = SearchService(ts, window=3)
    stop = words_of_class(lex, STOP)
    # miss and hit share one buffer: both must be immutable, or the first
    # caller could silently corrupt every later cache hit
    r_miss = svc.search([stop[0], stop[1]])
    r_hit = svc.search([stop[0], stop[1]])
    for r in (r_miss, r_hit):
        with pytest.raises(ValueError):
            r.witnesses[:] = 0


# ----------------------------------------- join packing regression (int64) --
def test_jax_join_beyond_int24_doc_packing():
    """Doc ids past the old 24-bit packing range: the int32 truncation bug
    made the jax join silently wrong there (scale picked off the
    post-truncation dtype).  The packed-key scale is now data-driven."""
    rng = np.random.RandomState(1)
    # 3000 docs x positions < 400: packed keys need doc*512, far beyond
    # what doc * 2^24 could hold in int32 (overflow at doc 128)
    docs = np.sort(rng.randint(0, 3000, 500))
    a = np.stack([docs, rng.randint(0, 400, 500)], 1)
    docs_b = np.sort(rng.randint(0, 3000, 400))
    b = np.stack([docs_b, rng.randint(0, 400, 400)], 1)
    a = a[np.lexsort((a[:, 1], a[:, 0]))]
    b = b[np.lexsort((b[:, 1], b[:, 0]))]
    for w in (0, 1, 3, 7):
        ref = numpy_window_join(a, b, w)
        jx = jax_window_join(a, b, w)
        assert ref.shape == jx.shape and (ref == jx).all(), w


def test_jax_join_padding_near_dtype_limit():
    """Packed keys just under the int32 admission line must not window-match
    the padding rows (b pads above every real key + window)."""
    M = np.iinfo(np.int32).max
    w = 3
    scale = 16  # pos < 16 - w - 1 keeps pos_scale at 16
    doc = (M - 5) // scale  # akey lands at M - 5 + pos adjustments
    a = np.asarray([[doc, 10], [doc, 11]], np.int64)
    # 3 rows pad to 4: the padded slot sits right past the real keys
    b = np.asarray([[1, 0], [2, 0], [3, 0]], np.int64)
    for arr in (a, b):
        assert arr[:, 0].max() * scale + arr[:, 1].max() + w < M
    ref = numpy_window_join(a, b, w)
    jx = jax_window_join(a, b, w)
    assert ref.shape == jx.shape == (0, 2)


def test_jax_join_falls_back_when_keys_exceed_int32():
    # doc ids so large the packed keys cannot fit int32: exact host fallback
    a = np.asarray([[2 ** 40, 5], [2 ** 40 + 1, 9]], np.int64)
    b = np.asarray([[2 ** 40, 7], [2 ** 41, 1]], np.int64)
    ref = numpy_window_join(a, b, 3)
    jx = jax_window_join(a, b, 3)
    assert np.array_equal(ref, jx)
    assert jx.shape == (1, 2) and jx[0, 0] == 2 ** 40


def test_pos_scale_headroom():
    for max_pos, w in [(0, 0), (5, 3), (511, 0), (511, 3), (1000, 7)]:
        s = pos_scale(max_pos, w)
        assert s > max_pos + w, (max_pos, w, s)
        assert s & (s - 1) == 0


# ------------------------------------------------- route census regression --
def test_route_census_regression(small_world):
    """Pin the planner's route per query shape so future planner edits
    cannot silently reroute traffic.  Columns: query, route, #lookups."""
    lex, ts = small_world
    svc = SearchService(ts, window=3)
    stop = words_of_class(lex, STOP)
    freq = words_of_class(lex, FREQUENT)
    other = words_of_class(lex, OTHER)
    P = True  # phrase
    table = [
        (Query((stop[0], stop[1])), ROUTE_STOPSEQ, 1),
        (Query((stop[0], stop[1], stop[2])), ROUTE_STOPSEQ, 1),
        (Query((stop[0], stop[1]), phrase=P), ROUTE_STOPSEQ, 1),
        (Query((freq[0], other[0])), ROUTE_WV, 1),
        (Query((other[0], freq[0])), ROUTE_WV, 1),
        (Query((other[0], other[1])), ROUTE_ORDINARY, 2),
        (Query((other[0], other[1], other[2])), ROUTE_ORDINARY, 3),
        (Query((stop[0], other[0])), ROUTE_ORDINARY, 2),
        (Query((freq[0], freq[1], other[0])), ROUTE_ORDINARY, 3),
        # k-word-covered phrase queries: one key per k-window of the cover
        (Query((other[0], other[1], other[2]), phrase=P), ROUTE_MULTI, 1),
        (Query((other[0], freq[0], stop[0]), phrase=P), ROUTE_MULTI, 1),
        (Query((other[0], other[1], other[2], other[3]), phrase=P), ROUTE_MULTI, 2),
        (Query((stop[0], stop[1], stop[2], stop[0]), phrase=P), ROUTE_MULTI, 2),
        # 2-word phrases: too short for a k=3 key, and (w, v) records
        # cannot reconstruct a phrase — ordinary phrase joins
        (Query((freq[0], other[0]), phrase=P), ROUTE_ORDINARY, 2),
        (Query((other[0], other[1]), phrase=P), ROUTE_ORDINARY, 2),
    ]
    plan = svc.plan([q for q, _, _ in table])
    for pq, (q, route, n_lookups) in zip(plan.queries, table):
        assert pq.route == route, (q, pq.route)
        assert len(pq.lookups) == n_lookups, (q, pq.lookups)
    census = plan.route_census()
    assert census == {
        ROUTE_STOPSEQ: 3, ROUTE_MULTI: 4, ROUTE_WV: 2, ROUTE_ORDINARY: 6,
    }
    # opting out of the multi index reroutes phrases down ordinary
    svc_no_multi = SearchService(ts, window=3, use_multi=False)
    plan2 = svc_no_multi.plan([Query((other[0], other[1], other[2]), phrase=P)])
    assert plan2.queries[0].route == ROUTE_ORDINARY
    assert len(plan2.queries[0].lookups) == 3


def test_wv_route_honors_narrow_window(small_world):
    """A per-query window NARROWER than max_distance cannot be applied to
    the precomputed (w, v) records (they carry only w's position), so
    those queries must take the ordinary route — and return exactly the
    narrow-window oracle, not max_distance false positives."""
    lex, ts = small_world
    svc = SearchService(ts, window=3)
    freq = words_of_class(lex, FREQUENT)
    other = words_of_class(lex, OTHER)
    md = ts.cfg.max_distance
    for q in ([freq[0], other[0]], [freq[1], freq[2]]):
        narrow = svc.plan([Query(tuple(q), window=1)]).queries[0]
        assert narrow.route == ROUTE_ORDINARY, q
        wide = svc.plan([Query(tuple(q), window=md)]).queries[0]
        assert wide.route == ROUTE_WV, q
        # execution agrees with the narrow-window join over raw postings
        r = svc.search_batch([Query(tuple(q), window=1)])[0]
        lemmas, _ = lex.classify_words(np.asarray(q, np.int64))
        posts = [ts.indexes["known"].lookup(int(l)) for l in lemmas]
        ref = numpy_window_join(posts[0], posts[1], 1)
        assert np.array_equal(r.docs, np.unique(ref[:, 0])), q


# --------------------------------- cross-backend equivalence (all 4 routes) --
@functools.lru_cache(maxsize=None)
def _equiv_world(seed: int):
    """A small random collection + per-class word pools + services for
    every join backend (cached: worlds are immutable across examples)."""
    lex = make_lexicon(
        n_words=3000, n_lemmas=1300, n_stop=20, n_frequent=120, seed=40 + seed
    )
    toks, offs = generate_part(lex, n_docs=60, avg_doc_len=120, doc0=0,
                               seed=60 + seed)
    cfg = IndexSetConfig(
        strategy=StrategyConfig.set2(cluster_size=1024),
        fl_area_clusters=64,
    )
    ts = TextIndexSet(cfg, lex, seed=0)
    ts.add_documents(toks, offs, 0)
    pools = class_pools(lex)
    services = {b: SearchService(ts, window=3, backend=b) for b in BACKENDS}
    return lex, toks, pools, services


@settings(max_examples=15, deadline=None)
@given(
    st.sampled_from((0, 1)),
    st.lists(QUERY_SPEC, min_size=0, max_size=10),
)
def test_cross_backend_equivalence_all_routes(world_seed, specs):
    """Property: numpy, jax and pallas return element-wise identical
    docs/witnesses/lookups for every planner route.  Each batch carries a
    fixed core hitting all four routes plus the drawn random queries."""
    lex, toks, pools, services = _equiv_world(world_seed)
    queries = core_queries(toks, pools) + [
        spec_to_query(s, toks, pools) for s in specs
    ]
    results = {b: services[b].search_batch(queries) for b in BACKENDS}
    routes = set()
    for qi, q in enumerate(queries):
        ref = results["numpy"][qi]
        routes.add(ref.route)
        for b in ("jax", "pallas"):
            assert_results_identical(ref, results[b][qi], ctx=(b, q))
    assert routes >= {ROUTE_STOPSEQ, ROUTE_WV, ROUTE_ORDINARY, ROUTE_MULTI}


def test_index_reader_own_device(small_world):
    """A standalone IndexReader charges its own device, not the writer's."""
    lex, ts = small_world
    idx = ts.indexes["known"]
    build_before = idx.mgr.device.stats.total_ops
    reader = IndexReader(idx)
    key = next(iter(idx.dict.entries))
    posts = reader.lookup(key)
    assert posts.shape[0] > 0
    assert idx.mgr.device.stats.total_ops == build_before
    assert reader.io_stats().total_ops > 0


@functools.lru_cache(maxsize=None)
def _far_world(doc0: int):
    """A tiny set whose doc ids start at ``doc0``, with an ordinary-route
    pair and a single-lookup word pair lifted from adjacent tokens."""
    lex = make_lexicon(n_words=2000, n_lemmas=900, n_stop=20,
                       n_frequent=80, seed=5)
    toks, offs = generate_part(lex, n_docs=40, avg_doc_len=200, doc0=doc0,
                               seed=3)
    cfg = IndexSetConfig(strategy=StrategyConfig.set2(cluster_size=256),
                         fl_area_clusters=64)
    ts = TextIndexSet(cfg, lex, seed=0)
    ts.add_documents(toks, offs, doc0)
    _, cls = lex.classify_words(toks)

    def pair(c):
        s = int(np.flatnonzero((cls[:-1] == c) & (cls[1:] == c))[0])
        return (int(toks[s]), int(toks[s + 1]))

    return ts, {"ordinary": Query(pair(OTHER)),
                "topk": Query(pair(FREQUENT), top_k=5)}


@pytest.mark.parametrize("backend,doc0,kind,site,per_batch", [
    # doc ids fit int32, packed (doc, pos) join keys do not
    ("jax", 20_000_000, "ordinary", "join_keys", (1, 1)),
    # doc ids beyond the intersect kernel's int32 keys
    ("pallas", 2 ** 31, "ordinary", "intersect_docs", (1, 1)),
    # the drained list is too wide for the device tier; the second batch
    # is a host-tier hit and drains nothing
    ("jax", 2 ** 31, "topk", "device_rows", (1, 0)),
])
def test_host_fallbacks_exact_and_counted_per_batch(backend, doc0, kind,
                                                    site, per_batch):
    ts, queries = _far_world(doc0)
    q = queries[kind]
    ref = SearchService(ts, backend="numpy").search_batch([q])[0]
    assert ref.docs.size and ref.route == (
        ROUTE_ORDINARY if kind == "ordinary" else ROUTE_WV)
    svc = SearchService(ts, backend=backend)
    for want in per_batch:
        assert svc.search_batch([q])[0] == ref
        fb = svc.last_trace["device"]["host_fallbacks"]
        assert fb == {s: (want if s == site else 0) for s in fb}, fb
