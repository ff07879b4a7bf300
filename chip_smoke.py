"""Serve the proximity-search path once on one TPU chip, and check it.

Usage:
    python chip_smoke.py

Builds the paper's full strategy set (``set3``) over part 1 of the
benchmark collection, serves mixed batches of 64 queries through
``SearchService.search_batch`` with the ``jax`` and ``pallas`` backends
(device decode on), adds part 2 as a live update (readers refresh with
targeted invalidation) and serves again.  Every result must equal the
``numpy`` backend's on the same index, no exact host fallback may fire,
and the intersect kernel must have launched compiled.  The last line of
standard output is one JSON object naming the device.

Exits non-zero, printing no result, when JAX finds no TPU.  The times it
prints are smoke timings of one run, not benchmark numbers.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# largest scale whose host build of both parts stays near three minutes
# (single-threaded Python, ~22k tokens/s on a v5e host): 2 parts x 5,400
# docs x ~350 tokens
SCALE = 4.5
# the paper's 71.5 GB collection in make_world's scale units
PAPER_SCALE = 12_000
BATCH_QUERIES = 64
DEVICE_BACKENDS = ("jax", "pallas")
# big enough that every drained list stays cached (host and device tier)
CACHE_BYTES = 1 << 30
TOP_K = 10
ROUNDS = ("part 1", "after live update")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeError(RuntimeError):
    """A phase of the smoke produced a wrong or unverifiable result."""


class _CompileCounter:
    """Counts XLA backend compiles (persistent-cache loads included)."""

    def __init__(self) -> None:
        self.n = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            self.n += 1


def query_mix(world, n: int, seed: int):
    """``n`` queries cycling the paper's query classes (stop pair, stop
    triple, frequent+other, frequent+frequent), other+other pairs (the
    ordinary route, whose window joins run on the device backends),
    multi-route phrases, and best-k (``top_k=10``) and ranked
    (``rank="prox"``) forms of them.  Words are lifted from adjacent
    tokens of part 1, so every query has matches."""
    import numpy as np

    from benchmarks.search_speed import _phrase_stream
    from repro.core.lexicon import FREQUENT, OTHER, STOP
    from repro.search import Query

    rng = np.random.RandomState(seed)
    toks = world.parts[0][0]
    _, cls = world.lexicon.classify_words(toks)

    def lifted(pattern):
        k = len(pattern)
        ok = np.ones(toks.shape[0] - k + 1, dtype=bool)
        for j, c in enumerate(pattern):
            ok &= cls[j: j + ok.shape[0]] == c
        starts = rng.choice(np.flatnonzero(ok), n)
        return [tuple(int(t) for t in toks[x: x + k]) for x in starts]

    classes = [lifted(p) for p in (
        (STOP, STOP), (STOP, STOP, STOP), (FREQUENT, OTHER),
        (FREQUENT, FREQUENT), (OTHER, OTHER),
    )]
    phrases = (_phrase_stream(world, n, 3, rng)
               + _phrase_stream(world, n, 4, rng))
    out = []
    for i in range(n):
        kind, cycle = i % 8, i // 8
        words = classes[kind if kind < 5 else cycle % 5][i]
        phrase = phrases[i + (n if cycle % 2 else 0)].words
        if kind < 5:
            out.append(Query(words))
        elif kind == 5:
            out.append(Query(phrase, phrase=True))
        elif kind == 6:
            out.append(Query(words, top_k=TOP_K))
        elif cycle % 2:
            out.append(Query(phrase, phrase=True, top_k=TOP_K, rank="prox"))
        else:
            out.append(Query(words, top_k=TOP_K, rank="prox"))
    return out


def _posting_bytes(ts) -> int:
    return sum(e.nbytes for idx in ts.indexes.values()
               for e in idx.dict.entries.values())


def run_smoke(scale: float, n_batches: int = 3, log=print) -> dict:
    """Build, serve, update, serve again; compare every device-backend
    result with the numpy backend's.  Raises :class:`SmokeError` on the
    first mismatch.  Returns the counters :func:`verify` checks."""
    import jax

    from benchmarks.common import bench_index_config, make_world
    from repro.core.text_index import TextIndexSet
    from repro.search import SearchService

    world = make_world(scale)
    n_docs = sum(int(offs.shape[0]) - 1 for _, offs in world.parts)
    t0 = time.perf_counter()
    ts = TextIndexSet(bench_index_config("set3"), world.lexicon, seed=0)
    ts.add_documents(*world.parts[0], world.doc_starts[0])
    build_s = time.perf_counter() - t0
    part_tokens = int(world.parts[0][0].shape[0])
    log(f"index: set3, scale {scale}: {world.total_tokens:,} tokens in "
        f"{n_docs:,} docs over {len(world.parts)} parts; part 1 "
        f"({part_tokens:,} tokens) built in {build_s:.1f} s, "
        f"{_posting_bytes(ts):,} posting bytes")
    log(f"size cut: scale {scale} is 1/{PAPER_SCALE / scale:,.0f} of the "
        f"paper's 71.5 GB collection (scale ~{PAPER_SCALE:,}); the host "
        f"build rate ({part_tokens / build_s:,.0f} tokens/s here) sets the "
        f"cut, not the chip")

    services = {
        b: SearchService(ts, window=3, backend=b, cache_bytes=CACHE_BYTES)
        for b in ("numpy",) + DEVICE_BACKENDS
    }
    report = {
        "queries_compared": 0,
        "compiles": {}, "batch_s": {},
        "compiled_launches": {b: {} for b in DEVICE_BACKENDS},
        "interpreted_launches": {b: {} for b in DEVICE_BACKENDS},
        "host_fallbacks": {b: {} for b in DEVICE_BACKENDS},
    }

    def add(into, counts):
        for k, v in counts.items():
            into[k] = into.get(k, 0) + v

    compiles = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        for r, rnd in enumerate(ROUNDS):
            if r:
                t0 = time.perf_counter()
                ts.add_documents(*world.parts[1], world.doc_starts[1])
                log(f"live update: part 2 applied in "
                    f"{time.perf_counter() - t0:.1f} s; readers refresh "
                    f"(targeted invalidation) at their next batch")
            batches = [query_mix(world, BATCH_QUERIES, seed=1000 * r + j)
                       for j in range(n_batches)]
            for b, svc in services.items():
                c0 = compiles.n
                times = []
                results = []
                for batch in batches:
                    t0 = time.perf_counter()
                    results.append(svc.search_batch(batch))
                    times.append(time.perf_counter() - t0)
                    if b != "numpy":
                        dev = svc.last_trace["device"]
                        add(report["compiled_launches"][b],
                            dev["compiled_launches"])
                        add(report["interpreted_launches"][b],
                            dev["interpreted_launches"])
                        add(report["host_fallbacks"][b],
                            dev["host_fallbacks"])
                report["batch_s"][(rnd, b)] = times
                report["compiles"][(rnd, b)] = compiles.n - c0
                if b == "numpy":
                    want = results
                    continue
                for j, (got_b, want_b) in enumerate(zip(results, want)):
                    for qi, (got, ref) in enumerate(zip(got_b, want_b)):
                        if got != ref or got.route != ref.route:
                            raise SmokeError(
                                f"{rnd}: backend {b} batch {j} query {qi} "
                                f"{batches[j][qi]} differs from numpy "
                                f"(docs {got.docs[:5]} vs {ref.docs[:5]})"
                            )
                        report["queries_compared"] += 1
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    report["device_hits"] = {
        b: services[b].reader.cache_stats.device_hits for b in DEVICE_BACKENDS
    }
    return report


def verify(report: dict, compiled: bool) -> None:
    """The smoke's pass conditions beyond result identity: no host
    fallback anywhere, and the intersect kernel launched in the mode the
    platform calls for (``compiled`` on the chip, interpreted on cpu)."""
    for b, sites in report["host_fallbacks"].items():
        bad = {s: n for s, n in sites.items() if n}
        if bad:
            raise SmokeError(f"backend {b} took host fallbacks {bad}")
    ran, idle = (("compiled_launches", "interpreted_launches") if compiled
                 else ("interpreted_launches", "compiled_launches"))
    if report[ran]["pallas"].get("intersect", 0) < 1:
        raise SmokeError(f"the intersect kernel never launched ({ran})")
    for b, kernels in report[idle].items():
        if any(kernels.values()):
            raise SmokeError(f"backend {b} launched {idle}: {kernels}")


def main() -> int:
    import jax

    devices = jax.devices()
    platforms = sorted({d.platform for d in devices})
    if platforms != ["tpu"]:
        print(f"chip_smoke: needs a TPU; JAX found platforms {platforms}",
              file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.common import enable_compile_cache

    dev = devices[0]
    print(f"device: {dev.device_kind} x {len(devices)} ({dev.platform})")
    print(f"compile cache: {enable_compile_cache()}")
    t_all = time.perf_counter()
    report = run_smoke(SCALE)
    verify(report, compiled=True)

    print("smoke timings (one run, not benchmark numbers), seconds per "
          f"batch of {BATCH_QUERIES}:")
    for (rnd, b), times in report["batch_s"].items():
        print(f"  {rnd:18s} {b:6s} "
              + " ".join(f"{t:.3f}" for t in times)
              + f"  (compiles {report['compiles'][(rnd, b)]})")
    warm = sum(n for (rnd, _), n in report["compiles"].items()
               if rnd != ROUNDS[0])
    print(f"compiles after warm-up (round 2, all backends): {warm}")
    for b in DEVICE_BACKENDS:
        print(f"{b}: compiled launches {report['compiled_launches'][b]}, "
              f"interpreted {report['interpreted_launches'][b]}, "
              f"host fallbacks {report['host_fallbacks'][b]}, "
              f"device-tier hits {report['device_hits'][b]}")
    stats = dev.memory_stats() or {}
    print(f"peak device bytes in use: {stats.get('peak_bytes_in_use')}")
    print(f"{report['queries_compared']} device-backend results identical "
          f"to numpy; total {time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
