"""Benchmark harness: one bench per paper table/figure + system benches.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--scale S] [--only name,...]

Benches:
    paper_tables  — Tables 2 and 3 (I/O bytes and ops, 3 strategy sets)
    chain_sweep   — section 5.7.3 chain-limit trade-off
    lifecycle     — Fig. 8 stream state distribution
    search_speed  — section 6.1 additional-index speedups
    search_batched — batched SearchService qps vs per-query loop
    search_sharded — 4-shard scatter/gather vs unsharded (qps + read bytes)
    search_topk   — top-k early-termination vs exhaustive (read-bytes ratio)
    search_ranked — score-ordered (WAND) top-k vs exhaustive ranked scan
    search_hot_traffic — concurrent hot-vocabulary queries through the
                    cross-query chunk pool vs per-query cursors
    search_replicas — replica read tier: capacity vs replica count,
                    failover sweep across backends × shard counts
    update_speed  — live per-shard update streams: targeted invalidation
                    vs whole-namespace drops under interleaved updates
    durability    — repro.store: WAL fsync cost, recovery time vs WAL
                    length, read bytes before/after compaction
    paged_kv      — TPU adaptation: paged KV allocator behaviour
    kernels       — Pallas kernel microbenches (interpret mode) vs refs
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def _bench_paper_tables(scale):
    from benchmarks import paper_tables

    rows = paper_tables.run(scale)
    verdicts = paper_tables.check_claims(rows)
    return rows, verdicts


def _bench_chain_sweep(scale):
    from benchmarks import chain_sweep

    rows = chain_sweep.run(min(scale, 0.5))
    ok = all(r["max_chain_segments"] <= r["chain_limit"] for r in rows)
    return rows, [f"{'PASS' if ok else 'FAIL'}  chain length bounded by limit"]


def _bench_lifecycle(scale):
    from benchmarks import lifecycle

    rows = lifecycle.run(min(scale, 0.5))
    ok1 = all(r.get("state_sr0", 0) == 0 for r in rows if r["set"] == "set1")
    ok2 = all(r.get("state_part", 0) == 0 for r in rows if r["set"] == "set2")
    return rows, [f"{'PASS' if (ok1 and ok2) else 'FAIL'}  Fig. 8 lifecycle paths"]


def _bench_search_speed(scale):
    from benchmarks import search_speed

    rows = search_speed.run(min(scale, 0.5))
    ok = all(r["agree"] for r in rows)
    fast = [
        r["scan_speedup"]
        for r in rows
        if r["class"] in ("stop_pair", "stop_triple", "freq_other", "freq_freq")
    ]
    ok &= min(fast) > 3
    return rows, [
        f"{'PASS' if ok else 'FAIL'}  additional-index speedup "
        f"(min {min(fast):.0f}x, max {max(fast):.0f}x)"
    ]


def _bench_search_batched(scale):
    from benchmarks import search_speed

    rows = search_speed.run_batched(min(scale, 0.5))
    ok = all(r["identical"] for r in rows)
    best = max(r["batch_speedup"] for r in rows)
    ok &= best > 1.0
    return rows, [
        f"{'PASS' if ok else 'FAIL'}  batched SearchService beats the "
        f"per-query loop (best {best:.2f}x) with identical results"
    ]


def _bench_search_sharded(scale):
    from benchmarks import search_speed

    rows = search_speed.run_sharded(min(scale, 0.5), n_shards=4)
    agg = rows[-1]
    # scale-invariant bytes gate: marginal overhead per extra shard must
    # stay within the fixed per-lookup dictionary budget (the raw ratio
    # is recorded in the trajectory but tracks corpus size, not
    # regressions — at tiny scales duplicated fixed costs dominate it)
    ok = agg["identical"] and (
        agg["overhead_bytes"] <= agg["overhead_budget_bytes"]
    )
    return rows, [
        f"{'PASS' if ok else 'FAIL'}  4-shard scatter/gather identical to "
        f"unsharded (sharding overhead {agg['overhead_bytes']:,} B <= "
        f"fixed per-lookup budget {agg['overhead_budget_bytes']:,} B; "
        f"raw bytes ratio {agg['bytes_ratio']:.3f} recorded, not gated — "
        f"not scale-invariant)"
    ]


def _bench_search_topk(scale):
    from benchmarks import search_speed

    rows = search_speed.run_topk(min(scale, 0.5), top_k=10, n_queries=32)
    r = rows[0]
    ok = (
        r["identical"]
        and r["chunks_skipped"] > 0
        and r["topk_read_bytes"] < r["ex_read_bytes"]
    )
    return rows, [
        f"{'PASS' if ok else 'FAIL'}  top-10 streaming head identical to "
        f"exhaustive at {r['bytes_ratio']:.3f}x read bytes "
        f"({r['chunks_skipped']} chunks skipped)"
    ]


def _bench_search_ranked(scale):
    from benchmarks import search_speed

    rows = search_speed.run_ranked(min(scale, 0.5), top_k=10, n_queries=24)
    r = rows[0]
    ok = (
        r["identical"]
        and r["chunks_skipped"] > 0
        and r["ranked_read_bytes"] < r["ex_read_bytes"]
    )
    return rows, [
        f"{'PASS' if ok else 'FAIL'}  ranked top-10 head identical to the "
        f"exhaustive score-then-sort scan at {r['bytes_ratio']:.3f}x read "
        f"bytes ({r['chunks_skipped']} chunks skipped, "
        f"{r['threshold_stops']} threshold stops)"
    ]


def _bench_search_hot_traffic(scale):
    from benchmarks import search_speed

    rows = search_speed.run_hot_traffic(min(scale, 0.5), n_queries=96)
    r = rows[0]
    ok = (
        r["identical"]
        and r["chunks_shared"] > 0
        and r["bytes_ratio"] <= 0.5
        and r["dedup_many_bytes"] < 2 * max(1, r["dedup_one_bytes"])
    )
    return rows, [
        f"{'PASS' if ok else 'FAIL'}  hot-traffic chunk pool identical to "
        f"per-query cursors at {r['bytes_ratio']:.3f}x read bytes "
        f"({r['chunks_shared']} chunk replays over {r['chunks_fetched']} "
        f"unique fetches)"
    ]


def _bench_search_replicas(scale):
    from benchmarks import search_speed

    s = min(scale, 0.5)
    world = search_speed.make_world(s)
    rows = search_speed.run_replicas(s, world=world, n_replicas=3,
                                     n_queries=48)
    summary = rows[-1]
    sweep = search_speed.run_replica_identity_sweep(s, world=world,
                                                    n_replicas=2)
    ok = (
        summary["identical"]
        and all(r["identical"] for r in sweep)
        and all(r["failovers"] >= 1 for r in sweep)
        and summary["capacity_ratio"] >= 1.5
    )
    return rows + sweep, [
        f"{'PASS' if ok else 'FAIL'}  3-replica fabric identical to the "
        f"single-reader path across backends x shard counts "
        f"(incl. {sum(r['failovers'] for r in sweep)} injected failovers) "
        f"at {summary['capacity_ratio']:.2f}x single-replica capacity, "
        f"p99 {summary['p99_ms']:.2f} ms"
    ]


def _bench_update_speed(scale):
    from benchmarks import update_speed

    rows = update_speed.run(min(scale, 0.5))
    t = next(r for r in rows if r["mode"] == "targeted")
    b = next(r for r in rows if r["mode"] == "namespace_drop")
    ok = (
        t["identical"]
        and t["invalidations"] < b["invalidations"]
        and t["full_drops"] < b["full_drops"]
        and t["read_bytes"] < b["read_bytes"]
    )
    return rows, [
        f"{'PASS' if ok else 'FAIL'}  interleaved updates served "
        f"stale-free and identical to a rebuild; targeted invalidation "
        f"dropped {t['invalidations']} cache entries vs "
        f"{b['invalidations']} whole-namespace"
    ]


def _bench_durability(scale):
    from benchmarks import durability

    rows = durability.run(min(scale, 0.5))
    by_mode = {r["mode"]: r for r in rows}
    a = by_mode["apply_wal_fsync"]
    ck = by_mode["checkpoint_reopen"]
    co = by_mode["compaction"]
    ok = (
        a["charge_parity"]
        and a["wal_syncs"] == a["parts"]
        and ck["identical"]
        and co["identical"]
        and co["compacted_streams"] >= 1
        and co["read_bytes_after"] <= co["read_bytes_before"]
    )
    return rows, [
        f"{'PASS' if ok else 'FAIL'}  durable store charged zero simulated "
        f"bytes; recovery served identical results "
        f"({ck['speedup']}x faster from checkpoint); compaction folded "
        f"{co['compacted_streams']} stream(s) at {co['bytes_ratio']}x "
        f"cold read bytes"
    ]


def _bench_paged_kv(scale):
    from benchmarks import paged_kv_bench

    return paged_kv_bench.run(scale)


def _bench_kernels(scale):
    from benchmarks import kernel_bench

    return kernel_bench.run(scale)


def _append_trajectory(path, scale, all_rows, verdicts):
    """Append one run record to the BENCH_search.json trajectory.

    The artifact is a JSON list — one record per harness run — so
    successive PRs accumulate a qps / read-bytes / p99 baseline per
    search scenario instead of overwriting it.  Scalar perf fields are
    harvested by name (qps, bytes, p99, ratios, speedups); everything
    else stays in the per-run --json dump.
    """
    scenarios = {}
    for r in all_rows:
        bench = str(r.get("bench", ""))
        if not bench.startswith(("search", "update")):
            continue
        scen = scenarios.setdefault(bench, {})
        for k, v in r.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            kl = k.lower()
            if ("qps" in kl or "bytes" in kl or "p99" in kl
                    or kl.endswith("_ratio") or "speedup" in kl):
                scen[k] = round(v, 4) if isinstance(v, float) else v
    scenarios = {k: v for k, v in scenarios.items() if v}
    record = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "scale": scale,
        "scenarios": scenarios,
        "verdicts": [f"{name}: {v}" for name, v in verdicts],
    }
    try:
        with open(path) as f:
            history = json.load(f)
        if not isinstance(history, list):
            history = [history]
    except (OSError, ValueError):
        history = []
    history.append(record)
    with open(path, "w") as f:
        json.dump(history, f, indent=1, default=str)
        f.write("\n")
    return record


BENCHES = {
    "paper_tables": _bench_paper_tables,
    "chain_sweep": _bench_chain_sweep,
    "lifecycle": _bench_lifecycle,
    "search_speed": _bench_search_speed,
    "search_batched": _bench_search_batched,
    "search_sharded": _bench_search_sharded,
    "search_topk": _bench_search_topk,
    "search_ranked": _bench_search_ranked,
    "search_hot_traffic": _bench_search_hot_traffic,
    "search_replicas": _bench_search_replicas,
    "update_speed": _bench_update_speed,
    "durability": _bench_durability,
    "paged_kv": _bench_paged_kv,
    "kernels": _bench_kernels,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--json", type=str, default="")
    ap.add_argument("--trajectory", type=str, default="BENCH_search.json",
                    help="perf-trajectory artifact to append to "
                         "('' disables)")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(BENCHES)
    from benchmarks.common import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")

    all_rows = []
    verdicts = []
    failed = []
    for name in names:
        fn = BENCHES[name]
        print(f"\n=== bench: {name} (scale={args.scale}) " + "=" * 30)
        t0 = time.time()
        try:
            rows, vds = fn(args.scale)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            continue
        dt = time.time() - t0
        for r in rows:
            all_rows.append(r)
            compact = {
                k: v for k, v in r.items() if not isinstance(v, dict)
            }
            print("  " + json.dumps(compact, default=str))
        for v in vds:
            print("  " + v)
            verdicts.append((name, v))
        print(f"  [{dt:.1f}s]")

    print("\n=== summary " + "=" * 40)
    for name, v in verdicts:
        print(f"{name:14s} {v}")
    n_fail = len(failed) + sum(1 for _, v in verdicts if v.startswith("FAIL"))
    print(f"\n{len(verdicts)} claims checked, {n_fail} failures"
          + (f" (errored: {failed})" if failed else ""))
    if args.trajectory:
        rec = _append_trajectory(args.trajectory, args.scale,
                                 all_rows, verdicts)
        print(f"trajectory: appended {len(rec['scenarios'])} scenario(s) "
              f"to {args.trajectory}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(all_rows, f, default=str, indent=1)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
