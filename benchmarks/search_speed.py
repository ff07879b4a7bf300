"""Search speed: additional indexes vs ordinary index (paper 6.1).

The paper's motivating claim: proximity queries containing frequently used
words are orders of magnitude cheaper through the (w,v) and stop-sequence
indexes than through the ordinary inverted index.  We measure postings
scanned, search I/O ops, and wall time per query class.

``--batched`` adds the multi-user serving view: the same mixed query
stream through ``SearchService.search_batch`` (planned, deduplicated,
JAX-bucketed joins) vs a per-query ``ProximityEngine.search`` loop,
reported as queries/sec per join backend.

``--multi`` compares the multi-component key route (arXiv:1812.07640)
against the ordinary-index join path on a stream of k-word phrase
queries: same results, strictly fewer posting bytes read (the k-word key
fetches only the phrase's own occurrences; the join path drags in every
occurrence of every queried lemma).

``--topk N`` measures the top-k early-termination streaming executor
(arXiv:2009.02684) against the exhaustive multi route on a
hot-vocabulary phrase stream: identical best-k heads (verified across
join backends and shard counts), strictly fewer posting bytes read, and
the chunks-skipped ledger from ``last_trace``.

``--hot-traffic C`` floods the streaming executor with C concurrent
hot-vocabulary top-k/ranked queries cycling a handful of phrases: the
cross-query chunk pool vs one private cursor per query — identical
results, read bytes scaling with unique chunks instead of queries
(ledgered as ``chunks_shared`` vs ``chunks_fetched`` in ``last_trace``),
and a dedup gate pinning N identical queries to < 2x one query's bytes.

``--shards N`` runs the same batched mixed stream through a
``ShardedTextIndexSet`` (document-hash sharding, scatter/gather
``SearchService``) vs the unsharded set, reporting per-shard and
aggregate queries/sec and read bytes.  The acceptance gate: sharding
must NOT inflate aggregate read I/O (per-shard posting subsets usually
land in *cheaper* storage tiers, so the sharded aggregate tends to come
in below the unsharded bytes).
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmarks.common import (
    HOT_GEOMETRY,
    World,
    build_index_set,
    build_sharded_index_set,
    make_hot_world,
    make_world,
)
from repro.core.lexicon import FREQUENT, OTHER, STOP
from repro.core.proximity import ProximityEngine
from repro.search import ROUTE_MULTI, Query, SearchService


def _words_of_class(lex, cls, n, rng):
    ids = [
        int(w)
        for w in range(lex.n_words)
        if lex.lemma1[w] >= 0 and lex.lemma_class[lex.lemma1[w]] == cls
    ]
    rng.shuffle(ids)
    return ids[:n]


def run(scale: float = 0.5, world: World = None) -> List[Dict]:
    world = world or make_world(scale)
    ts = build_index_set(world, "set2", build_ordinary_all=True,
                         multi_k=None)  # no phrase queries in this bench
    eng = ProximityEngine(ts, window=3)
    lex = world.lexicon
    rng = np.random.RandomState(7)
    stop = _words_of_class(lex, STOP, 12, rng)
    freq = _words_of_class(lex, FREQUENT, 12, rng)
    other = _words_of_class(lex, OTHER, 12, rng)

    classes = {
        "stop_pair": [[stop[i], stop[i + 1]] for i in range(0, 10, 2)],
        "stop_triple": [[stop[i], stop[i + 1], stop[i + 2]] for i in range(0, 9, 3)],
        "freq_other": [[freq[i], other[i]] for i in range(5)],
        "freq_freq": [[freq[i], freq[i + 1]] for i in range(0, 10, 2)],
        "other_other": [[other[i], other[i + 1]] for i in range(0, 10, 2)],
    }
    rows: List[Dict] = []
    for cname, queries in classes.items():
        scan_add = scan_ord = t_add = t_ord = 0.0
        agree = True
        for q in queries:
            t0 = time.perf_counter()
            r1 = eng.search(q)
            t_add += time.perf_counter() - t0
            t0 = time.perf_counter()
            r2 = eng.search_ordinary(q)
            t_ord += time.perf_counter() - t0
            scan_add += r1.postings_scanned
            scan_ord += r2.postings_scanned
            agree &= set(r1.docs.tolist()) == set(r2.docs.tolist())
        n = len(queries)
        rows.append(
            {
                "bench": "search_speed",
                "class": cname,
                "queries": n,
                "add_scanned": int(scan_add / n),
                "ord_scanned": int(scan_ord / n),
                "scan_speedup": scan_ord / max(1.0, scan_add),
                "add_us": t_add / n * 1e6,
                "ord_us": t_ord / n * 1e6,
                "agree": agree,
            }
        )
    return rows


def _mixed_stream(lex, n_queries: int, rng) -> List[List[int]]:
    """A mixed multi-user query stream over all three planner routes, with
    the repeat structure of real traffic (hot keys recur across users)."""
    stop = _words_of_class(lex, STOP, 12, rng)
    freq = _words_of_class(lex, FREQUENT, 12, rng)
    other = _words_of_class(lex, OTHER, 12, rng)
    qs: List[List[int]] = []
    while len(qs) < n_queries:
        kind = len(qs) % 4
        if kind == 0:
            qs.append([rng.choice(stop), rng.choice(stop)])
        elif kind == 1:
            qs.append([rng.choice(stop), rng.choice(stop), rng.choice(stop)])
        elif kind == 2:
            qs.append([rng.choice(freq), rng.choice(other)])
        else:
            qs.append([rng.choice(other), rng.choice(other)])
    return [[int(w) for w in q] for q in qs]


def run_batched(
    scale: float = 0.5,
    world: World = None,
    n_queries: int = 64,
    backends=("numpy", "jax", "pallas"),
    repeats: int = 3,
) -> List[Dict]:
    """Per-query loop vs ``search_batch`` on the same query stream."""
    if n_queries < 1:
        raise ValueError(f"--queries must be >= 1, got {n_queries}")
    world = world or make_world(scale)
    ts = build_index_set(world, "set2", build_ordinary_all=False,
                         multi_k=None)  # no phrase queries in this bench
    lex = world.lexicon
    queries = _mixed_stream(lex, n_queries, np.random.RandomState(7))

    rows: List[Dict] = []
    for backend in backends:
        eng = ProximityEngine(ts, window=3, join=backend)
        svc = SearchService(ts, window=3, backend=backend)
        # warm both paths: jit compilation + posting cache fill, so the
        # timed section measures steady-state serving throughput
        loop_ref = [eng.search(q) for q in queries]
        batch_ref = svc.search_batch(queries)
        identical = all(
            np.array_equal(ref.docs, got.docs)
            and np.array_equal(ref.witnesses, got.witnesses)
            for ref, got in zip(loop_ref, batch_ref)
        )
        t_loop = min(
            _timed(lambda: [eng.search(q) for q in queries])
            for _ in range(repeats)
        )
        t_batch = min(
            _timed(lambda: svc.search_batch(queries)) for _ in range(repeats)
        )
        rows.append(
            {
                "bench": "search_speed_batched",
                "backend": backend,
                "queries": len(queries),
                "loop_qps": len(queries) / t_loop,
                "batch_qps": len(queries) / t_batch,
                "batch_speedup": t_loop / t_batch,
                "identical": identical,
            }
        )
    return rows


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# ------------------------------------------------- multi-component route --
def _phrase_stream(world: World, n_queries: int, k: int, rng) -> List[Query]:
    """k-word phrase queries lifted from the real token stream (so they
    have occurrences), skipping all-stop windows (those take the even
    cheaper stop-sequence route, not the one under test)."""
    lex = world.lexicon
    toks, offs = world.parts[0]
    out: List[Query] = []
    while len(out) < n_queries:
        s = int(rng.randint(0, toks.shape[0] - k))
        words = tuple(int(t) for t in toks[s : s + k])
        _, cls = lex.classify_words(np.asarray(words, np.int64))
        if all(int(c) == STOP for c in cls):
            continue
        out.append(Query(words, phrase=True))
    return out


def _read_bytes(ts) -> int:
    return sum(s.read_bytes for s in ts.search_io().values())


def run_multi(
    scale: float = 0.5,
    world: World = None,
    n_queries: int = 64,
    repeats: int = 3,
) -> List[Dict]:
    """ROUTE_MULTI vs the ordinary-index join path on phrase queries.

    Both services run the numpy (oracle) backend with the posting cache
    disabled, so the reader ``search_io`` deltas are the true per-batch
    posting traffic of each path.
    """
    if n_queries < 1:
        raise ValueError(f"--queries must be >= 1, got {n_queries}")
    world = world or make_world(scale)
    ts = build_index_set(world, "set2", build_ordinary_all=False)
    k = ts.indexes["multi"].k
    queries = _phrase_stream(world, n_queries, k, np.random.RandomState(11))

    svc_multi = SearchService(ts, window=3, backend="numpy", cache_bytes=0)
    svc_ord = SearchService(ts, window=3, backend="numpy", cache_bytes=0,
                            use_multi=False)

    b0 = _read_bytes(ts)
    res_multi = svc_multi.search_batch(queries)
    multi_bytes = _read_bytes(ts) - b0
    b0 = _read_bytes(ts)
    res_ord = svc_ord.search_batch(queries)
    ord_bytes = _read_bytes(ts) - b0

    # identical answers (the ordinary path may carry duplicate witness
    # rows when a token's two lemma readings coincide — compare sets)
    identical = all(
        rm.route == ROUTE_MULTI
        and ro.route == "ordinary"
        and np.array_equal(rm.docs, ro.docs)
        and {tuple(x) for x in rm.witnesses.tolist()}
        == {tuple(x) for x in ro.witnesses.tolist()}
        for rm, ro in zip(res_multi, res_ord)
    )
    t_multi = min(
        _timed(lambda: svc_multi.search_batch(queries)) for _ in range(repeats)
    )
    t_ord = min(
        _timed(lambda: svc_ord.search_batch(queries)) for _ in range(repeats)
    )
    scanned_multi = sum(r.postings_scanned for r in res_multi)
    scanned_ord = sum(r.postings_scanned for r in res_ord)
    return [
        {
            "bench": "search_speed_multi",
            "queries": len(queries),
            "k": k,
            "multi_qps": len(queries) / t_multi,
            "ord_qps": len(queries) / t_ord,
            "multi_read_bytes": int(multi_bytes),
            "ord_read_bytes": int(ord_bytes),
            "bytes_ratio": ord_bytes / max(1, multi_bytes),
            "multi_scanned": int(scanned_multi),
            "ord_scanned": int(scanned_ord),
            "identical": identical,
        }
    ]


def main_multi(scale: float = 0.5, n_queries: int = 64) -> None:
    rows = run_multi(scale, n_queries=n_queries)
    r = rows[0]
    print(f"{'route':10s} {'qps':>10s} {'read_bytes':>12s} {'scanned':>10s}")
    print(f"{'multi':10s} {r['multi_qps']:>10,.0f} {r['multi_read_bytes']:>12,} "
          f"{r['multi_scanned']:>10,}")
    print(f"{'ordinary':10s} {r['ord_qps']:>10,.0f} {r['ord_read_bytes']:>12,} "
          f"{r['ord_scanned']:>10,}")
    print(f"{r['queries']} {r['k']}-word phrase queries; "
          f"bytes ratio ord/multi = {r['bytes_ratio']:.1f}x")
    assert r["identical"], "ROUTE_MULTI diverged from the ordinary-join oracle"
    assert r["multi_read_bytes"] < r["ord_read_bytes"], (
        "multi route must read strictly fewer posting bytes"
    )
    print("PASS  multi route matches the ordinary join and reads fewer bytes")


# ------------------------------------------------- top-k early termination --
def run_topk(
    scale: float = 0.5,
    world: World = None,
    n_queries: int = 64,
    top_k: int = 10,
    repeats: int = 3,
    verify_backends=("numpy", "jax", "pallas"),
    verify_shards=(1, 2, 4),
) -> List[Dict]:
    """``Query(top_k=N)`` streaming execution vs the exhaustive multi
    route on a hot-vocabulary phrase stream (arXiv:2009.02684).

    Both services run the numpy oracle backend with the posting cache
    disabled, so the reader ``search_io`` deltas are the true per-batch
    posting traffic; the acceptance gate is read bytes STRICTLY below the
    exhaustive path (early termination must actually skip chunks, not
    degrade to a full scan), with the top-k head element-wise identical
    across every join backend and shard count in ``verify_*``.
    """
    if n_queries < 1:
        raise ValueError(f"--queries must be >= 1, got {n_queries}")
    if top_k < 1:
        raise ValueError(f"--topk must be >= 1, got {top_k}")
    world = world or make_hot_world(scale)
    # hot-corpus geometry: small clusters/EM limit keep per-key lists
    # spanning several cursor chunks even at CI scale
    cfg_kw = HOT_GEOMETRY
    ts = build_index_set(world, "set2", **cfg_kw)
    k = ts.indexes["multi"].k
    base = _phrase_stream(world, n_queries, k, np.random.RandomState(11))
    topk_queries = [
        Query(q.words, phrase=True, top_k=top_k) for q in base
    ]

    svc_topk = SearchService(ts, window=3, backend="numpy", cache_bytes=0)
    svc_ex = SearchService(ts, window=3, backend="numpy", cache_bytes=0)

    b0 = _read_bytes(ts)
    res_topk = svc_topk.search_batch(topk_queries)
    topk_bytes = _read_bytes(ts) - b0
    trace = dict(svc_topk.last_trace["topk"])
    b0 = _read_bytes(ts)
    res_ex = svc_ex.search_batch(base)
    ex_bytes = _read_bytes(ts) - b0

    # the streamed head must equal the exhaustive head element-wise
    identical = all(
        rt.route == ROUTE_MULTI
        and np.array_equal(rt.docs, re.docs[:top_k])
        and np.array_equal(
            rt.witnesses,
            re.witnesses[np.isin(re.witnesses[:, 0], re.docs[:top_k])],
        )
        and np.array_equal(rt.scores, re.scores[:top_k])
        for rt, re in zip(res_topk, res_ex)
    )

    # ... and stay identical across join backends and shard counts
    verify_queries = topk_queries[: min(len(topk_queries), 16)]
    ref = res_topk[: len(verify_queries)]
    for n_shards in verify_shards:
        if n_shards == 1:
            substrate = ts
        else:
            substrate = build_sharded_index_set(
                world, "set2", n_shards=n_shards, **cfg_kw
            )
        for backend in verify_backends:
            svc = SearchService(substrate, window=3, backend=backend,
                                cache_bytes=0)
            got = svc.search_batch(verify_queries)
            identical &= all(
                np.array_equal(r.docs, g.docs)
                and np.array_equal(r.witnesses, g.witnesses)
                and np.array_equal(r.scores, g.scores)
                for r, g in zip(ref, got)
            )

    t_topk = min(
        _timed(lambda: svc_topk.search_batch(topk_queries))
        for _ in range(repeats)
    )
    t_ex = min(
        _timed(lambda: svc_ex.search_batch(base)) for _ in range(repeats)
    )
    return [
        {
            "bench": "search_speed_topk",
            "queries": len(base),
            "top_k": top_k,
            "topk_qps": len(base) / t_topk,
            "ex_qps": len(base) / t_ex,
            "topk_read_bytes": int(topk_bytes),
            "ex_read_bytes": int(ex_bytes),
            "bytes_ratio": topk_bytes / max(1, ex_bytes),
            "chunks_fetched": trace["chunks_fetched"],
            "chunks_skipped": trace["chunks_skipped"],
            "early_terminated": trace["early_terminated"],
            "identical": identical,
        }
    ]


def main_topk(scale: float = 0.5, n_queries: int = 64,
              top_k: int = 10) -> None:
    r = run_topk(scale, n_queries=n_queries, top_k=top_k)[0]
    print(f"{'mode':10s} {'qps':>10s} {'read_bytes':>12s}")
    print(f"{'top-' + str(r['top_k']):10s} {r['topk_qps']:>10,.0f} "
          f"{r['topk_read_bytes']:>12,}")
    print(f"{'exhaustive':10s} {r['ex_qps']:>10,.0f} "
          f"{r['ex_read_bytes']:>12,}")
    print(f"{r['queries']} phrase queries; read-bytes ratio "
          f"topk/exhaustive = {r['bytes_ratio']:.3f}; "
          f"{r['chunks_skipped']} chunks skipped "
          f"({r['early_terminated']} queries early-terminated)")
    assert r["identical"], (
        "top-k head diverged from the exhaustive sorted head"
    )
    assert r["chunks_skipped"] > 0, (
        "early termination must skip chunks, not degrade to a full scan"
    )
    assert r["topk_read_bytes"] < r["ex_read_bytes"], (
        "top-k must read strictly fewer posting bytes than exhaustive"
    )
    print("PASS  top-k head identical to exhaustive with strictly fewer "
          "read bytes")


# ----------------------------------------------------- ranked (WAND) top-k --
def run_ranked(
    scale: float = 0.5,
    world: World = None,
    n_queries: int = 48,
    top_k: int = 10,
    repeats: int = 3,
    verify_backends=("numpy", "jax", "pallas"),
    verify_shards=(1, 2, 4),
) -> List[Dict]:
    """``Query(top_k=N, rank="prox")`` — score-ordered best-k with the
    WAND-style threshold stop — vs the exhaustive ranked scan
    (arXiv:2108.00410 on top of the streaming executor).

    The exhaustive reference is the SAME ranked executor asked for a
    head larger than the collection: the threshold can never settle, so
    it drains every cursor, scores every match and sorts — an on-line
    exhaustive score-then-sort oracle.  Both services run numpy with the
    posting cache disabled so the reader ``search_io`` deltas are the
    true posting traffic; the acceptance gate is the ranked head
    element-wise identical (docs, scores, tie order, witnesses) at
    STRICTLY fewer read bytes, verified across every join backend and
    shard count in ``verify_*``.
    """
    if n_queries < 1:
        raise ValueError(f"--queries must be >= 1, got {n_queries}")
    if top_k < 1:
        raise ValueError(f"--ranked must be >= 1, got {top_k}")
    world = world or make_hot_world(scale)
    cfg_kw = HOT_GEOMETRY
    ts = build_index_set(world, "set2", **cfg_kw)
    k = ts.indexes["multi"].k
    base = _phrase_stream(world, n_queries, k, np.random.RandomState(13))
    ranked_queries = [
        Query(q.words, phrase=True, top_k=top_k, rank="prox") for q in base
    ]
    drain_k = 1 << 30  # >= any match count: the full ranked scan
    drain_queries = [
        Query(q.words, phrase=True, top_k=drain_k, rank="prox") for q in base
    ]

    svc_rk = SearchService(ts, window=3, backend="numpy", cache_bytes=0)
    svc_ex = SearchService(ts, window=3, backend="numpy", cache_bytes=0)

    b0 = _read_bytes(ts)
    res_rk = svc_rk.search_batch(ranked_queries)
    rk_bytes = _read_bytes(ts) - b0
    svc_rk.check_trace_complete()
    trace = dict(svc_rk.last_trace["topk"])
    b0 = _read_bytes(ts)
    res_ex = svc_ex.search_batch(drain_queries)
    ex_bytes = _read_bytes(ts) - b0

    # the pruned head must equal the exhaustive ranked scan's prefix
    # element-wise: docs, scores, tie order, and the head's witnesses
    identical = all(
        rt.route == ROUTE_MULTI
        and np.array_equal(rt.docs, re.docs[:top_k])
        and np.array_equal(rt.scores, re.scores[:top_k])
        and np.array_equal(
            rt.witnesses,
            re.witnesses[np.isin(re.witnesses[:, 0], re.docs[:top_k])],
        )
        for rt, re in zip(res_rk, res_ex)
    )

    # ... and stay identical across join backends and shard counts
    verify_queries = ranked_queries[: min(len(ranked_queries), 16)]
    ref = res_rk[: len(verify_queries)]
    for n_shards in verify_shards:
        if n_shards == 1:
            substrate = ts
        else:
            substrate = build_sharded_index_set(
                world, "set2", n_shards=n_shards, **cfg_kw
            )
        for backend in verify_backends:
            svc = SearchService(substrate, window=3, backend=backend,
                                cache_bytes=0)
            got = svc.search_batch(verify_queries)
            svc.check_trace_complete()
            identical &= all(
                np.array_equal(r.docs, g.docs)
                and np.array_equal(r.witnesses, g.witnesses)
                and np.array_equal(r.scores, g.scores)
                for r, g in zip(ref, got)
            )

    t_rk = min(
        _timed(lambda: svc_rk.search_batch(ranked_queries))
        for _ in range(repeats)
    )
    t_ex = min(
        _timed(lambda: svc_ex.search_batch(drain_queries))
        for _ in range(repeats)
    )
    return [
        {
            "bench": "search_speed_ranked",
            "queries": len(base),
            "top_k": top_k,
            "ranked_qps": len(base) / t_rk,
            "ex_qps": len(base) / t_ex,
            "ranked_read_bytes": int(rk_bytes),
            "ex_read_bytes": int(ex_bytes),
            "bytes_ratio": rk_bytes / max(1, ex_bytes),
            "chunks_fetched": trace["chunks_fetched"],
            "chunks_skipped": trace["chunks_skipped"],
            "threshold_stops": trace["threshold_stops"],
            "threshold_checks": trace["threshold_checks"],
            "identical": identical,
        }
    ]


def main_ranked(scale: float = 0.5, n_queries: int = 48,
                top_k: int = 10) -> None:
    r = run_ranked(scale, n_queries=n_queries, top_k=top_k)[0]
    print(f"{'mode':12s} {'qps':>10s} {'read_bytes':>12s}")
    print(f"{'ranked-' + str(r['top_k']):12s} {r['ranked_qps']:>10,.0f} "
          f"{r['ranked_read_bytes']:>12,}")
    print(f"{'full scan':12s} {r['ex_qps']:>10,.0f} "
          f"{r['ex_read_bytes']:>12,}")
    print(f"{r['queries']} ranked phrase queries; read-bytes ratio "
          f"ranked/exhaustive = {r['bytes_ratio']:.3f}; "
          f"{r['chunks_skipped']} chunks skipped "
          f"({r['threshold_stops']} threshold stops / "
          f"{r['threshold_checks']} checks)")
    assert r["identical"], (
        "ranked head diverged from the exhaustive score-then-sort scan"
    )
    assert r["chunks_skipped"] > 0, (
        "the WAND threshold stop must skip chunks, not drain every list"
    )
    assert r["ranked_read_bytes"] < r["ex_read_bytes"], (
        "ranked top-k must read strictly fewer posting bytes than the "
        "exhaustive ranked scan"
    )
    print("PASS  ranked head identical to the exhaustive ranked scan with "
          "strictly fewer read bytes")


# ------------------------------------------- hot-traffic chunk sharing --
def run_hot_traffic(
    scale: float = 0.5,
    world: World = None,
    n_queries: int = 256,
    top_k: int = 10,
    repeats: int = 3,
    n_distinct: int = 8,
    verify_backends=("numpy", "jax", "pallas"),
    verify_shards=(1, 2, 4),
) -> List[Dict]:
    """Hundreds of concurrent hot-vocabulary best-k queries: the
    cross-query :class:`~repro.search.pool.ChunkPool` vs one private
    cursor per query.

    The stream cycles ``n_distinct`` hot phrases (mixing plain top-k and
    ranked queries), so every batch hammers the same few multi-key
    posting streams — the regime where per-query cursors re-read the
    same chunks N times.  Both services run cache-disabled numpy so the
    reader ``search_io`` deltas are pure posting traffic; acceptance:

      * results element-wise identical to the unpooled baseline (and,
        for the first queries, across every backend × shard count with
        device decode on);
      * pooled read bytes <= 0.5x the baseline (hot batches must scale
        with unique chunks, not queries);
      * the dedup gate — a batch of N identical queries reads < 2x the
        bytes of a single-query batch, not Nx;
      * ``last_trace`` ledgers the sharing (``chunks_shared`` replays vs
        ``chunks_fetched`` unique fetches) and stays complete under the
        extended ``check_trace_complete`` partition.
    """
    if n_queries < 1:
        raise ValueError(f"--hot-traffic must be >= 1, got {n_queries}")
    world = world or make_hot_world(scale)
    cfg_kw = HOT_GEOMETRY
    ts = build_index_set(world, "set2", **cfg_kw)
    k = ts.indexes["multi"].k
    distinct = _phrase_stream(world, n_distinct, k,
                              np.random.RandomState(17))
    queries = [
        Query(distinct[i % len(distinct)].words, phrase=True, top_k=top_k,
              rank="prox" if i % 3 == 0 else None)
        for i in range(n_queries)
    ]

    svc_base = SearchService(ts, window=3, backend="numpy", cache_bytes=0,
                             share_chunks=False, device_decode=False)
    svc_pool = SearchService(ts, window=3, backend="numpy", cache_bytes=0,
                             share_chunks=True, device_decode=False)

    b0 = _read_bytes(ts)
    res_base = svc_base.search_batch(queries)
    base_bytes = _read_bytes(ts) - b0
    b0 = _read_bytes(ts)
    res_pool = svc_pool.search_batch(queries)
    pool_bytes = _read_bytes(ts) - b0
    trace = dict(svc_pool.last_trace["topk"])

    identical = all(
        np.array_equal(rb.docs, rp.docs)
        and np.array_equal(rb.witnesses, rp.witnesses)
        and np.array_equal(rb.scores, rp.scores)
        for rb, rp in zip(res_base, res_pool)
    )

    # ... and identical with the device decoder + device cache tier on,
    # across every join backend and shard count
    verify_queries = queries[: min(len(queries), 12)]
    ref = res_base[: len(verify_queries)]
    for n_shards in verify_shards:
        if n_shards == 1:
            substrate = ts
        else:
            substrate = build_sharded_index_set(
                world, "set2", n_shards=n_shards, **cfg_kw
            )
        for backend in verify_backends:
            svc = SearchService(substrate, window=3, backend=backend,
                                cache_bytes=1 << 20, share_chunks=True,
                                device_decode=backend in ("jax", "pallas"))
            got = svc.search_batch(verify_queries)
            svc.check_trace_complete()
            identical &= all(
                np.array_equal(r.docs, g.docs)
                and np.array_equal(r.witnesses, g.witnesses)
                and np.array_equal(r.scores, g.scores)
                for r, g in zip(ref, got)
            )

    # dedup gate: N identical hot queries must cost ~1x one query's I/O
    one = [queries[0]]
    many = [queries[0]] * max(8, min(n_queries, 64))
    svc1 = SearchService(ts, window=3, backend="numpy", cache_bytes=0,
                         share_chunks=True, device_decode=False)
    b0 = _read_bytes(ts)
    svc1.search_batch(one)
    b1 = _read_bytes(ts) - b0
    svcN = SearchService(ts, window=3, backend="numpy", cache_bytes=0,
                         share_chunks=True, device_decode=False)
    b0 = _read_bytes(ts)
    svcN.search_batch(many)
    bN = _read_bytes(ts) - b0

    # per-query latency: element-wise best over repeats (noise floor),
    # p99 across the batch's queries
    def _query_s(svc) -> np.ndarray:
        per_rep = []
        for _ in range(repeats):
            svc.search_batch(queries)
            per_rep.append(np.asarray(svc.last_trace["topk"]["query_s"]))
        return np.min(np.stack(per_rep), axis=0)

    base_s = _query_s(svc_base)
    pool_s = _query_s(svc_pool)
    p99_base = float(np.percentile(base_s, 99))
    p99_pool = float(np.percentile(pool_s, 99))

    return [
        {
            "bench": "search_speed_hot_traffic",
            "queries": n_queries,
            "distinct": len(distinct),
            "top_k": top_k,
            "base_read_bytes": int(base_bytes),
            "pool_read_bytes": int(pool_bytes),
            "bytes_ratio": pool_bytes / max(1, base_bytes),
            "chunks_fetched": trace["chunks_fetched"],
            "chunks_shared": trace["chunks_shared"],
            "pool_streams": trace["pool_streams"],
            "dedup_one_bytes": int(b1),
            "dedup_many": len(many),
            "dedup_many_bytes": int(bN),
            "p99_base_us": p99_base * 1e6,
            "p99_pool_us": p99_pool * 1e6,
            "identical": identical,
        }
    ]


def main_hot(scale: float = 0.5, n_queries: int = 256,
             top_k: int = 10) -> None:
    r = run_hot_traffic(scale, n_queries=n_queries, top_k=top_k)[0]
    print(f"{'mode':12s} {'read_bytes':>12s} {'p99_us':>10s}")
    print(f"{'per-query':12s} {r['base_read_bytes']:>12,} "
          f"{r['p99_base_us']:>10,.0f}")
    print(f"{'pooled':12s} {r['pool_read_bytes']:>12,} "
          f"{r['p99_pool_us']:>10,.0f}")
    print(f"{r['queries']} hot queries over {r['distinct']} distinct "
          f"phrases; bytes ratio pooled/per-query = {r['bytes_ratio']:.3f}; "
          f"{r['chunks_shared']} chunk replays over {r['chunks_fetched']} "
          f"unique fetches ({r['pool_streams']} pooled streams); "
          f"{r['dedup_many']} identical queries read {r['dedup_many_bytes']:,}"
          f" bytes vs {r['dedup_one_bytes']:,} for one")
    assert r["identical"], (
        "pooled results diverged from the per-query-cursor baseline"
    )
    assert r["chunks_shared"] > 0, (
        "a hot batch must replay pooled chunks, not open private drains"
    )
    assert r["bytes_ratio"] <= 0.5, (
        f"pooled read bytes must be <= 0.5x the per-query baseline, got "
        f"{r['bytes_ratio']:.3f}"
    )
    assert r["dedup_many_bytes"] < 2 * max(1, r["dedup_one_bytes"]), (
        f"{r['dedup_many']} identical queries read "
        f"{r['dedup_many_bytes']} bytes — more than 2x one query's "
        f"{r['dedup_one_bytes']}"
    )
    # device reads are SIMULATED (byte-accounted, zero wall time), so the
    # pool's wall-clock edge is only the skipped host decode work — gate
    # p99 against a real regression, not strict improvement in the noise
    if n_queries >= 100:
        assert r["p99_pool_us"] <= 1.10 * r["p99_base_us"], (
            f"pooled p99 {r['p99_pool_us']:.0f}us regressed over baseline "
            f"{r['p99_base_us']:.0f}us"
        )
    print("PASS  hot-traffic batch shares chunks across queries with "
          "identical results and <= 0.5x read bytes")


# ------------------------------------------------------ sharded substrate --
# Sharded read-I/O gate.  A raw sharded/unsharded byte RATIO is not
# scale-invariant: with doc-hash sharding every shard serves every
# (index, key) lookup, so the per-lookup FIXED costs — the 24-byte
# dictionary entry header, the key bytes, the shard's per-wave
# dictionary-group read — duplicate across shards while the posting
# payload splits.  At tiny corpora the duplicated fixed cost dominates
# (the old <= 1.1 ratio assert read 1.56 at trajectory scale and still
# 1.15-1.37 at 0.5 scale, tracking query mix, not regressions).  The
# honest, scale-invariant bound is on the MARGINAL overhead per extra
# shard per executed lookup: measured ~70-85 bytes across scales
# (entry header + key + amortized group-dictionary bytes), budgeted at
# 128 to leave headroom.  A real regression — duplicated posting
# payload, uncharged re-fetches — scales with payload bytes and blows
# through a fixed per-lookup budget at any corpus size, so this gate
# runs (and fails loudly) at trajectory scale too.
SHARDED_OVERHEAD_BUDGET_PER_LOOKUP = 128


def run_sharded(
    scale: float = 0.5,
    world: World = None,
    n_shards: int = 4,
    n_queries: int = 64,
    backend: str = "jax",
    repeats: int = 3,
) -> List[Dict]:
    """Sharded scatter/gather serving vs the unsharded set, same stream.

    Both services run with the posting cache disabled so the search-device
    deltas are the true per-batch posting traffic of each substrate; the
    sharded service uses the pipelined prefetch fetch stage (its default).
    """
    if n_shards < 1:
        raise ValueError(f"--shards must be >= 1, got {n_shards}")
    if n_queries < 1:
        raise ValueError(f"--queries must be >= 1, got {n_queries}")
    world = world or make_world(scale)
    ts = build_index_set(world, "set2", build_ordinary_all=False,
                         multi_k=None)  # mixed stream has no phrase queries
    sts = build_sharded_index_set(world, "set2", n_shards=n_shards,
                                  multi_k=None)
    queries = _mixed_stream(world.lexicon, n_queries, np.random.RandomState(7))

    svc_u = SearchService(ts, window=3, backend=backend, cache_bytes=0)
    svc_s = SearchService(sts, window=3, backend=backend, cache_bytes=0)

    b0 = _read_bytes(ts)
    ref = svc_u.search_batch(queries)  # also warms jit
    unsharded_bytes = _read_bytes(ts) - b0
    b0 = _read_bytes(sts)
    got = svc_s.search_batch(queries)
    sharded_bytes = _read_bytes(sts) - b0
    per_shard_bytes = [
        sum(s.read_bytes for s in shard_io.values())
        for shard_io in sts.search_io_per_shard()
    ]

    identical = all(
        np.array_equal(r.docs, g.docs)
        and np.array_equal(r.witnesses, g.witnesses)
        and r.lookups == g.lookups
        and r.postings_scanned == g.postings_scanned
        for r, g in zip(ref, got)
    )
    t_u = min(_timed(lambda: svc_u.search_batch(queries))
              for _ in range(repeats))
    t_s = min(_timed(lambda: svc_s.search_batch(queries))
              for _ in range(repeats))
    # per-shard serving rate: the batch size over the seconds THAT shard's
    # device fetches took inside the pipelined scatter stage (traced by the
    # service) — the balance view across shards
    shard_fetch_s = svc_s.last_trace.get("shard_fetch_s", [0.0] * n_shards)
    # per-lookup fixed-overhead budget for the bytes gate: each of the
    # n_shards-1 EXTRA shards re-pays the fixed dictionary cost of every
    # executed lookup (the posting payload itself splits across shards)
    # planned = fetched + deferred-to-streaming; both end up paying the
    # per-shard fixed dictionary cost once
    lookups_fetched = int(svc_s.last_trace.get("lookups_planned", 0))
    overhead_budget = (
        (n_shards - 1) * lookups_fetched * SHARDED_OVERHEAD_BUDGET_PER_LOOKUP
    )
    rows: List[Dict] = [
        {
            "bench": "search_speed_sharded",
            "shard": s,
            "n_shards": n_shards,
            "queries": len(queries),
            "shard_qps": len(queries) / max(1e-9, shard_fetch_s[s]),
            "read_bytes": int(per_shard_bytes[s]),
        }
        for s in range(n_shards)
    ]
    rows.append(
        {
            "bench": "search_speed_sharded",
            "shard": "aggregate",
            "n_shards": n_shards,
            "queries": len(queries),
            "sharded_qps": len(queries) / t_s,
            "unsharded_qps": len(queries) / t_u,
            "sharded_read_bytes": int(sharded_bytes),
            "unsharded_read_bytes": int(unsharded_bytes),
            "bytes_ratio": sharded_bytes / max(1, unsharded_bytes),
            "overhead_bytes": int(sharded_bytes - unsharded_bytes),
            "overhead_budget_bytes": int(overhead_budget),
            "overhead_per_lookup_per_shard": round(
                (sharded_bytes - unsharded_bytes)
                / max(1, (n_shards - 1) * lookups_fetched), 1),
            "prefetched_waves": svc_s.last_trace.get("prefetched_waves", 0),
            "identical": identical,
        }
    )
    return rows


def main_sharded(scale: float = 0.5, n_queries: int = 64,
                 n_shards: int = 4, backend: str = "jax") -> None:
    rows = run_sharded(scale, n_shards=n_shards, n_queries=n_queries,
                       backend=backend)
    agg = rows[-1]
    print(f"{'shard':>9s} {'qps':>12s} {'read_bytes':>12s}")
    for r in rows[:-1]:
        print(f"{r['shard']:>9d} {r['shard_qps']:>12,.0f} "
              f"{r['read_bytes']:>12,}")
    print(f"{'aggregate':>9s} {agg['sharded_qps']:>12,.0f} "
          f"{agg['sharded_read_bytes']:>12,}")
    print(f"unsharded baseline: {agg['unsharded_qps']:,.0f} qps, "
          f"{agg['unsharded_read_bytes']:,} read bytes "
          f"(sharded/unsharded bytes ratio {agg['bytes_ratio']:.3f}, "
          f"{agg['prefetched_waves']} prefetched waves)")
    assert agg["identical"], "sharded results diverged from unsharded"
    assert agg["overhead_bytes"] <= agg["overhead_budget_bytes"], (
        f"sharding inflated read I/O beyond the fixed per-shard "
        f"dictionary overhead: {agg['overhead_bytes']:,} extra bytes > "
        f"budget {agg['overhead_budget_bytes']:,} "
        f"({SHARDED_OVERHEAD_BUDGET_PER_LOOKUP} B x {n_shards - 1} extra "
        f"shards x planned lookups); payload bytes are duplicating, not "
        f"splitting"
    )
    print(f"PASS  {n_shards}-shard scatter/gather matches unsharded "
          f"results; sharding overhead {agg['overhead_bytes']:,} B is "
          f"within the fixed per-lookup budget "
          f"{agg['overhead_budget_bytes']:,} B "
          f"({agg['overhead_per_lookup_per_shard']} B/lookup/extra-shard; "
          f"raw bytes ratio {agg['bytes_ratio']:.3f} is recorded for the "
          f"trajectory but not gated — it is not scale-invariant)")


# ------------------------------------------------------ replica fabric --
def _fault_after(n: int):
    """One-shot injected fault: the replica serves ``n`` more ops, then
    dies mid-batch (the fabric must fail the batch over to a sibling)."""
    from repro.search import ReplicaDeadError

    served = [0]

    def fault(rep, op):
        served[0] += 1
        if served[0] > n:
            raise ReplicaDeadError(f"injected after {n} serves")

    return fault


# most the busiest replica may read over the replicas' mean read bytes
BALANCE_GATE = 1.25


def run_replicas(
    scale: float = 0.5,
    world: World = None,
    n_replicas: int = 3,
    n_queries: int = 64,
    backend: str = "numpy",
    repeats: int = 3,
) -> List[Dict]:
    """Replica read tier: N replicas per shard behind the fabric scatter.

    Balance: routing spreads the read load over the replicas, so each
    pays about 1/N of the simulated read bytes; ``bytes_balance`` is the
    busiest replica's bytes over the mean (1.0 is perfectly even).
    Caches are off so the charge model is deterministic and every replica
    pays its own device reads.

    Identity: the fabric batch — including a replica killed mid-batch by
    an injected fault — must stay element-wise identical to the plain
    single-reader path.
    """
    from repro.search import ReplicaSetReader

    if n_replicas < 1:
        raise ValueError(f"--replicas must be >= 1, got {n_replicas}")
    world = world or make_world(scale)
    ts = build_index_set(world, "set2", multi_k=None)
    queries = _mixed_stream(world.lexicon, n_queries,
                            np.random.RandomState(7))
    ref = SearchService(ts, window=3, backend="numpy",
                        cache_bytes=0).search_batch(queries)

    def identical(got):
        return all(
            np.array_equal(r.docs, g.docs)
            and np.array_equal(r.witnesses, g.witnesses)
            and r.postings_scanned == g.postings_scanned
            for r, g in zip(ref, got)
        )

    rows: List[Dict] = []
    for n in sorted({1, n_replicas}):
        fab = ReplicaSetReader(ts, n_replicas=n, cache_bytes=0)
        svc = SearchService(fab, window=3, backend=backend, cache_bytes=0)
        ok = identical(svc.search_batch(queries))  # also warms jit
        t_wall = 0.0
        for _ in range(repeats):
            t_wall += _timed(lambda: svc.search_batch(queries))
        per_rep_bytes = [b for row in fab.read_bytes_per_replica()
                         for b in row]
        rows.append({
            "bench": "search_speed_replicas",
            "n_replicas": n,
            "queries": len(queries),
            "wall_qps": repeats * len(queries) / t_wall,
            "read_bytes_per_replica": per_rep_bytes,
            "bytes_balance": max(per_rep_bytes) / max(1.0, (
                sum(per_rep_bytes) / len(per_rep_bytes)
            )),
            "failovers": fab.failovers,
            "identical": ok,
        })

    # per-query latency distribution through the full fabric (p99 is the
    # serving-tier health number the trajectory artifact tracks)
    fab = ReplicaSetReader(ts, n_replicas=n_replicas, cache_bytes=0)
    svc = SearchService(fab, window=3, backend=backend, cache_bytes=0)
    svc.search_batch(queries)
    lat = sorted(_timed(lambda q=q: svc.search_batch([q])) for q in queries)
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))]

    summary = {
        "bench": "search_speed_replicas",
        "n_replicas": "summary",
        "queries": len(queries),
        "bytes_balance": rows[-1]["bytes_balance"],
        "p99_ms": p99 * 1e3,
        "identical": all(r["identical"] for r in rows),
    }
    rows.append(summary)
    return rows


def run_replica_identity_sweep(
    scale: float = 0.5,
    world: World = None,
    n_queries: int = 16,
    n_replicas: int = 2,
    backends=("numpy", "jax", "pallas"),
    shard_counts=(1, 2, 4),
) -> List[Dict]:
    """The failover oracle sweep: every backend × shard count serves the
    same stream through the fabric WITH one replica killed mid-batch —
    results must stay element-wise identical to the unsharded numpy
    single-reader reference."""
    from repro.search import ReplicaSetReader

    world = world or make_world(scale)
    queries = _mixed_stream(world.lexicon, n_queries,
                            np.random.RandomState(11))
    ts = build_index_set(world, "set2", multi_k=None)
    ref = SearchService(ts, window=3, backend="numpy",
                        cache_bytes=0).search_batch(queries)
    subs = {1: ts}
    for n in shard_counts:
        if n > 1:
            subs[n] = build_sharded_index_set(world, "set2", n_shards=n,
                                              multi_k=None)
    rows: List[Dict] = []
    for n_shards in shard_counts:
        for backend in backends:
            fab = ReplicaSetReader(subs[n_shards], n_replicas=n_replicas,
                                   cache_bytes=0)
            svc = SearchService(fab, window=3, backend=backend,
                                cache_bytes=0)
            fab.replicas[0][0].fault = _fault_after(3)
            got = svc.search_batch(queries)
            ok = all(
                np.array_equal(r.docs, g.docs)
                and np.array_equal(r.witnesses, g.witnesses)
                for r, g in zip(ref, got)
            )
            rows.append({
                "bench": "search_speed_replica_sweep",
                "n_shards": n_shards,
                "backend": backend,
                "failovers": fab.failovers,
                "dead": sum(not rep.live for row in fab.replicas
                            for rep in row),
                "identical": ok,
            })
    return rows


def main_replicas(scale: float = 0.5, n_queries: int = 64,
                  n_replicas: int = 3, backend: str = "numpy") -> None:
    world = make_world(scale)
    rows = run_replicas(scale, world=world, n_replicas=n_replicas,
                        n_queries=n_queries, backend=backend)
    summary = rows[-1]
    print(f"{'replicas':>8s} {'wall_qps':>10s} {'bytes_bal':>9s} "
          f"{'identical':>9s}")
    for r in rows[:-1]:
        print(f"{r['n_replicas']:>8d} {r['wall_qps']:>10,.0f} "
              f"{r['bytes_balance']:>9.2f} {str(r['identical']):>9s}")
    print(f"p99 {summary['p99_ms']:.2f} ms")

    sweep = run_replica_identity_sweep(scale, world=world,
                                       n_replicas=max(2, n_replicas // 2 + 1))
    for r in sweep:
        print(f"  sweep shards={r['n_shards']} backend={r['backend']:6s} "
              f"failovers={r['failovers']} identical={r['identical']}")

    assert summary["identical"], "fabric results diverged from single-reader"
    assert all(r["identical"] for r in sweep), (
        "failover sweep diverged from the reference"
    )
    assert all(r["failovers"] >= 1 for r in sweep), (
        "the injected fault must actually force a failover"
    )
    # balance gate: routing must spread the read bytes over the N
    # replicas — the busiest within BALANCE_GATE of the mean
    assert summary["bytes_balance"] <= BALANCE_GATE, (
        f"busiest replica read {summary['bytes_balance']:.2f}x the mean "
        f"at {n_replicas} replicas (gate {BALANCE_GATE})"
    )
    print(f"PASS  {n_replicas}-replica fabric serves identical results "
          f"(incl. mid-batch failover) with the busiest replica at "
          f"{summary['bytes_balance']:.2f}x the mean read bytes")


def main_batched(scale: float = 0.5, n_queries: int = 64) -> None:
    rows = run_batched(scale, n_queries=n_queries)
    print(f"{'backend':8s} {'queries':>8s} {'loop_qps':>10s} {'batch_qps':>10s} "
          f"{'speedup':>8s}")
    for r in rows:
        print(
            f"{r['backend']:8s} {r['queries']:>8d} {r['loop_qps']:>10,.0f} "
            f"{r['batch_qps']:>10,.0f} {r['batch_speedup']:>8.2f}"
        )
    assert all(r["identical"] for r in rows), (
        "search_batch diverged from the per-query loop"
    )
    assert max(r["batch_speedup"] for r in rows) > 1.0, (
        "batched execution should beat the per-query loop"
    )
    print("PASS  search_batch matches the per-query loop and is faster")


def main(scale: float = 0.5) -> None:
    rows = run(scale)
    print(
        f"{'class':12s} {'add_scan':>9s} {'ord_scan':>9s} {'speedup':>8s} "
        f"{'add_us':>9s} {'ord_us':>9s} {'agree':>6s}"
    )
    for r in rows:
        print(
            f"{r['class']:12s} {r['add_scanned']:>9,} {r['ord_scanned']:>9,} "
            f"{r['scan_speedup']:>8.1f} {r['add_us']:>9.0f} {r['ord_us']:>9.0f} "
            f"{str(r['agree']):>6s}"
        )
    assert all(r["agree"] for r in rows)
    fast = [r for r in rows if r["class"] in ("stop_pair", "stop_triple", "freq_other", "freq_freq")]
    assert min(r["scan_speedup"] for r in fast) > 3
    print("PASS  additional indexes agree with, and scan far less than, ordinary")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--batched", action="store_true",
                    help="batched SearchService qps vs per-query loop")
    ap.add_argument("--multi", action="store_true",
                    help="multi-component key route vs ordinary join "
                         "on phrase queries")
    ap.add_argument("--topk", type=int, default=0,
                    help="N: top-k early-termination streaming executor "
                         "vs the exhaustive multi route on a hot phrase "
                         "stream (qps + read-bytes ratio; verifies the "
                         "head across backends and shard counts)")
    ap.add_argument("--ranked", type=int, default=0,
                    help="N: score-ordered (rank='prox') top-k with the "
                         "WAND threshold stop vs the exhaustive ranked "
                         "scan on a hot phrase stream (qps + read-bytes "
                         "ratio; head identity-verified across backends "
                         "and shard counts)")
    ap.add_argument("--hot-traffic", type=int, default=0,
                    help="C: C concurrent hot-vocabulary top-k/ranked "
                         "queries through the cross-query chunk pool vs "
                         "one private cursor per query (read-bytes + p99 "
                         "latency; identity-verified across backends and "
                         "shard counts, dedup gate on identical queries)")
    ap.add_argument("--shards", type=int, default=0,
                    help="N-shard scatter/gather SearchService vs the "
                         "unsharded set, both through search_batch; "
                         "composes with --batched (the sharded bench IS "
                         "the batched comparison)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="N: N-replica read fabric vs a single reader — "
                         "per-replica read-bytes balance, "
                         "bytes-balance, p99, and the failover oracle "
                         "sweep (every backend × shard count with one "
                         "replica killed mid-batch)")
    ap.add_argument("--backend", default="jax",
                    help="join backend for --shards (numpy/jax/pallas)")
    ap.add_argument("--queries", type=int, default=64)
    args = ap.parse_args()
    if args.replicas:
        main_replicas(args.scale, n_queries=args.queries,
                      n_replicas=args.replicas, backend=args.backend)
    elif args.shards:
        # --shards compares batched serving on both substrates, so
        # `--shards N --batched` is the canonical spelling; --batched
        # alone keeps its loop-vs-batch meaning below
        main_sharded(args.scale, n_queries=args.queries,
                     n_shards=args.shards, backend=args.backend)
    elif args.batched:
        main_batched(args.scale, n_queries=args.queries)
    elif args.multi:
        main_multi(args.scale, n_queries=args.queries)
    elif args.topk:
        main_topk(args.scale, n_queries=args.queries, top_k=args.topk)
    elif args.ranked:
        main_ranked(args.scale, n_queries=args.queries, top_k=args.ranked)
    elif args.hot_traffic:
        main_hot(args.scale, n_queries=args.hot_traffic)
    else:
        main(args.scale)
