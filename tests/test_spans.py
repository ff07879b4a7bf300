"""Host spans and counters inside ``search_batch``.

``last_trace["spans"]`` holds, per batch, the seconds and counts of the
program's spans (``repro.core.spans``), merged over the serving thread
and the fetch worker; each span is also a ``TraceAnnotation`` on the
profiler's clock.  ``index.decode`` counts the encoded posting bytes it
decoded, and the decodes (and bytes) that took the byte-parallel path.  ``last_trace["device"]`` counts the jax join's launches
(``window_mask``).  The span names are declared in the trace schema, and
the device programs keep stable names in a profiler trace.
"""

import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import spans as spans_mod
from repro.core.dictionary import K_EM
from repro.core.lexicon import make_lexicon
from repro.core.postings import PARALLEL_MIN_BYTES
from repro.core.strategies import StrategyConfig
from repro.core.text_index import IndexSetConfig, TextIndexSet
from repro.data.corpus import generate_part
from repro.search import Query, SearchService
from repro.search import service as service_mod
from repro.search.schema import TRACE_SCHEMA, validate_trace
from tests.oracles import mixed_queries

SERVING_SPANS = ("search.plan", "search.fetch_wait", "search.finalize",
                 "search.join", "search.topk")


@pytest.fixture(scope="module")
def world():
    lex = make_lexicon(n_words=4000, n_lemmas=1800, n_stop=30,
                       n_frequent=150, seed=21)
    toks, offs = generate_part(lex, n_docs=120, avg_doc_len=220, doc0=0,
                               seed=4)
    cfg = IndexSetConfig(strategy=StrategyConfig.set2(cluster_size=1024),
                         build_ordinary_all=True, fl_area_clusters=64)
    ts = TextIndexSet(cfg, lex, seed=0)
    ts.add_documents(toks, offs, 0)
    qs = [Query(tuple(q)) for q in mixed_queries(lex, 24, seed=9)]
    return ts, qs


def _with_topk(qs):
    return qs + [Query(qs[0].words, top_k=3, rank="prox"),
                 Query(qs[1].words, top_k=2)]


def _encoded_bytes(ts, ident) -> int:
    """Bytes the index decodes for one (index, key) lookup."""
    name, key = ident
    idx = ts.indexes[name]
    e = idx.dict.get(key)
    if e is None:
        return 0
    if e.kind == K_EM:
        return len(e.data)
    return len(idx.mgr.streams[e.sid].data)


# ------------------------------------------------------------ the block --
@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("prefetch", [True, False])
def test_batch_reports_every_span_its_routes_reach(world, backend, prefetch):
    ts, qs = world
    svc = SearchService(ts, backend=backend, cache_bytes=0,
                        prefetch=prefetch)
    batch = _with_topk(qs)
    svc.search_batch(batch)
    tr = svc.last_trace
    sp = tr["spans"]
    assert set(sp) == TRACE_SCHEMA["spans"]
    n_batch = sum(q.top_k is None for q in batch)
    assert sp["search.plan"]["n"] == 1
    assert sp["search.join"]["n"] == 1
    assert sp["search.finalize"]["n"] == n_batch
    assert sp["search.topk"]["n"] == len(batch) - n_batch
    assert sp["search.fetch"]["n"] == tr["executed_waves"]
    assert sp["search.fetch_wait"]["n"] == tr["executed_waves"]
    assert all(v["s"] >= 0 for v in sp.values())
    # only the decode carries a byte count
    assert {k for k, v in sp.items() if "bytes" in v} == {"index.decode"}
    # the top-k stage's per-query times are the spans' own
    assert sum(tr["topk"]["query_s"]) == pytest.approx(sp["search.topk"]["s"])


def test_serving_thread_spans_fit_in_the_batch(world):
    ts, qs = world
    svc = SearchService(ts, backend="jax", cache_bytes=0)
    batch = _with_topk(qs)
    svc.search_batch(batch)  # warm the jit buckets
    t0 = time.perf_counter()
    svc.search_batch(batch)
    wall = time.perf_counter() - t0
    sp = svc.last_trace["spans"]
    serving = sum(sp[name]["s"] for name in SERVING_SPANS)
    assert 0 < serving <= wall


@pytest.mark.parametrize("cache_bytes", [0, 64 << 20])
def test_decode_bytes_are_the_lists_that_missed_the_cache(world, cache_bytes):
    ts, qs = world
    svc = SearchService(ts, backend="numpy", cache_bytes=cache_bytes)
    plan = svc.plan(qs)
    idents = {(lk.index, lk.key) for pq in plan.queries for lk in pq.lookups}
    want = sum(_encoded_bytes(ts, i) for i in idents)
    assert want > 0
    svc.search_batch(qs)
    assert svc.last_trace["spans"]["index.decode"]["bytes"] == want
    svc.search_batch(qs)
    dec = svc.last_trace["spans"].get("index.decode")
    if cache_bytes:
        # every list is a cache hit now: nothing is decoded
        assert dec is None
    else:
        assert dec["bytes"] == want


def test_parallel_decodes_are_the_lists_past_the_threshold(world):
    ts, qs = world
    svc = SearchService(ts, backend="numpy", cache_bytes=0)
    plan = svc.plan(qs)
    sizes = [_encoded_bytes(ts, i) for i in
             {(lk.index, lk.key) for pq in plan.queries for lk in pq.lookups}]
    big = [n for n in sizes if n >= PARALLEL_MIN_BYTES]
    # the batch decodes lists on both sides: small EM lists in the loop
    assert big and any(0 < n < PARALLEL_MIN_BYTES for n in sizes)
    svc.search_batch(qs)
    dec = svc.last_trace["spans"]["index.decode"]
    assert dec["n"] == sum(n > 0 for n in sizes)
    assert dec["parallel"] == len(big)
    assert dec["parallel_bytes"] == sum(big)
    assert dec["bytes"] == sum(sizes)
    assert validate_trace(svc.last_trace) == ""


def test_span_recorder_tallies_fields_across_threads():
    rec = spans_mod.SpanRecorder()

    def work():
        with rec.active():
            spans_mod.note_parallel_decode(300)

    t = threading.Thread(target=work)
    t.start()
    t.join()
    with rec.span("index.decode", 500):
        rec.tally("index.decode", parallel=1, parallel_bytes=500)
    dec = rec.totals()["index.decode"]
    assert dec["n"] == 1 and dec["bytes"] == 500
    assert dec["parallel"] == 2 and dec["parallel_bytes"] == 800
    # outside a batch nothing is counted
    spans_mod.note_parallel_decode(999)
    assert rec.totals()["index.decode"]["parallel"] == 2


def test_fetch_waves_on_the_worker_merge_into_the_batch(world, monkeypatch):
    ts, qs = world
    svc = SearchService(ts, backend="numpy", cache_bytes=0, prefetch=True)
    seen = set()
    inner = svc.reader.lookup_shard

    def lookup_shard(shard, index_name, key):
        seen.add(threading.get_ident())
        return inner(shard, index_name, key)

    monkeypatch.setattr(svc.reader, "lookup_shard", lookup_shard)
    svc.search_batch(qs)
    tr = svc.last_trace
    assert tr["executed_waves"] > 1
    # the waves ran on the worker thread, never on this one ...
    assert seen and threading.get_ident() not in seen
    # ... and their fetch and decode spans are in this batch's block
    assert tr["spans"]["search.fetch"]["n"] == tr["executed_waves"]
    assert tr["spans"]["index.decode"]["bytes"] > 0


def test_two_services_keep_their_own_spans(world):
    ts, qs = world
    a = SearchService(ts, backend="numpy", cache_bytes=0)
    b = SearchService(ts, backend="numpy", cache_bytes=0)
    a.search_batch(qs)
    b.search_batch(qs[:3])
    assert a.last_trace["spans"]["search.finalize"]["n"] == len(qs)
    assert b.last_trace["spans"]["search.finalize"]["n"] == 3
    # outside a batch no recorder is current, and a lookup times nothing
    assert spans_mod.current() is None
    rec = a._spans
    before = rec.totals()
    lk = a.plan(qs).queries[0].lookups[0]
    assert a.reader.lookup(lk.index, lk.key).shape[0] > 0
    assert rec.totals() == before


# --------------------------------------------------------- the recorder --
def test_span_recorder_keeps_threads_apart_and_merges_them():
    rec = spans_mod.SpanRecorder()

    def work():
        for _ in range(3):
            with rec.span("search.fetch"):
                pass
        with rec.span("index.decode", 7):
            pass

    t = threading.Thread(target=work)
    t.start()
    t.join()
    with rec.span("index.decode", 5) as sp:
        time.sleep(0.001)
    assert sp.seconds >= 0.001
    tot = rec.totals()
    assert tot["search.fetch"]["n"] == 3 and "bytes" not in tot["search.fetch"]
    assert tot["index.decode"]["n"] == 2
    assert tot["index.decode"]["bytes"] == 12
    assert tot["index.decode"]["s"] >= sp.seconds


def test_span_recorder_loses_no_span_under_thread_switches():
    rec = spans_mod.SpanRecorder()
    n_threads, per_thread = 4 * (os.cpu_count() or 4), 500
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per_thread):
                with rec.span("index.decode", 3):
                    pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    dec = rec.totals()["index.decode"]
    assert dec["n"] == n_threads * per_thread
    assert dec["bytes"] == 3 * n_threads * per_thread


def test_active_recorder_is_reset_after_the_block():
    rec = spans_mod.SpanRecorder()
    assert spans_mod.current() is None
    with pytest.raises(RuntimeError):
        with rec.active():
            assert spans_mod.current() is rec
            with spans_mod.decode_span(10):
                pass
            raise RuntimeError("batch failed")
    assert spans_mod.current() is None
    dec = rec.totals()["index.decode"]
    assert dec["n"] == 1 and dec["bytes"] == 10


def test_spans_land_on_the_profiler_clock(world, tmp_path):
    ts, qs = world
    svc = SearchService(ts, backend="jax", cache_bytes=0)
    svc.search_batch(qs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.search_batch(qs)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            names.update(ev.name for ev in line.events)
    assert {"search.plan", "search.fetch_wait", "search.fetch",
            "search.finalize", "search.join", "index.decode"} <= names


# ------------------------------------------------------ schema and lint --
def test_trace_check_refuses_an_undeclared_span():
    assert validate_trace({"spans": {"search.plan": {"s": 0.1, "n": 1}}}) == ""
    assert "search.bogus" in validate_trace(
        {"spans": {"search.bogus": {"s": 0.1, "n": 1}}})
    assert "ms" in validate_trace(
        {"spans": {"search.plan": {"ms": 0.1, "n": 1}}})


def test_trace_check_accepts_the_parallel_decode_fields_only():
    entry = {"s": 0.1, "n": 3, "bytes": 900, "parallel": 1,
             "parallel_bytes": 800}
    assert validate_trace({"spans": {"index.decode": entry}}) == ""
    bad = dict(entry, parallel_ms=0.2)
    assert "parallel_ms" in validate_trace({"spans": {"index.decode": bad}})


# --------------------------------------------------- device launches --
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_window_mask_launches_counted_per_bucket(world, backend, monkeypatch):
    ts, qs = world
    launched = []
    inner = service_mod.batched_window_mask

    def counting(*args):
        launched.append(args[0].shape)
        return inner(*args)

    monkeypatch.setattr(service_mod, "batched_window_mask", counting)
    svc = SearchService(ts, backend=backend, cache_bytes=0)
    svc.search_batch(qs)
    dev = svc.last_trace["device"]
    assert dev["compiled_launches"]["window_mask"] == len(launched)
    assert dev["interpreted_launches"]["window_mask"] == 0
    assert (len(launched) > 0) == (backend == "jax")
    assert svc.last_trace["spans"]["search.join"]["n"] == 1


def _lowered(name):
    i32 = jnp.int32
    if name == "batched_window_mask":
        from repro.search.join import batched_window_mask

        return batched_window_mask.lower(
            jax.ShapeDtypeStruct((2, 8), i32),
            jax.ShapeDtypeStruct((2, 8), i32),
            jax.ShapeDtypeStruct((2,), i32))
    if name == "prox_score":
        from repro.search.scoring import _jitted_score

        return _jitted_score(2, 8, 4).lower(
            jax.ShapeDtypeStruct((2, 8), i32), jax.ShapeDtypeStruct((2,), i32))
    if name == "varint_unpack_kernel":
        from repro.kernels.posting_decode.kernel import varint_unpack_kernel

        return varint_unpack_kernel.lower(
            jax.ShapeDtypeStruct((8,), i32), jax.ShapeDtypeStruct((8,), i32),
            8, bn=8, bm=8, interpret=True)
    from repro.kernels.intersect.kernel import intersect_kernel

    return intersect_kernel.lower(
        jax.ShapeDtypeStruct((8,), i32), jax.ShapeDtypeStruct((8,), i32),
        bn=8, bm=8, interpret=True)


@pytest.mark.parametrize("name", ["batched_window_mask", "prox_score",
                                  "varint_unpack_kernel", "intersect_kernel"])
def test_device_programs_keep_their_names(name):
    # a profiler trace names a launch by its module: jit_<name>
    text = _lowered(name).as_text()
    assert f"module @jit_{name} " in text


def test_spans_cost_microseconds_with_the_profiler_off():
    rec = spans_mod.SpanRecorder()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with rec.span("search.finalize"):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert rec.totals()["search.finalize"]["n"] == n
    # about a microsecond here; far below the millisecond grain of a stage
    assert per_span < 50e-6
    assert np.isfinite(per_span)
