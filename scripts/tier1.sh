#!/usr/bin/env bash
# Tier-1 verification — the single entry point CI and humans share.
# Keep in sync with ROADMAP.md ("Tier-1 verify").
set -euo pipefail
cd "$(dirname "$0")/.."
# invariant lint gate first: the repro.analysis passes (charge
# accounting, trace schema, generation discipline, cache tiers, kernel
# purity) fail in milliseconds, before any benchmark or test runs
scripts/lint.sh
# tiny-corpus smoke of the sharded scatter/gather serving path (--shards
# composes with --batched: both substrates run through search_batch):
# asserts sharded results stay identical to unsharded and read I/O does
# not inflate
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.search_speed \
  --shards 2 --batched --scale 0.05 --queries 16
# tiny-corpus smoke of the top-k streaming executor: asserts the best-k
# head stays element-wise identical to the exhaustive path (across
# backends and shard counts) while reading strictly fewer posting bytes
# with chunks actually skipped
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.search_speed \
  --topk 10 --scale 0.05 --queries 12
# tiny-corpus smoke of the score-ordered (rank='prox') top-k executor:
# asserts the WAND-threshold-pruned head stays element-wise identical —
# docs, scores, tie order — to the exhaustive ranked scan (across
# backends and shard counts) while skipping chunks and reading strictly
# fewer posting bytes
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.search_speed \
  --ranked 5 --scale 0.05 --queries 10
# tiny-corpus smoke of the cross-query chunk pool: a hot-vocabulary
# batch through pooled cursors must stay element-wise identical to the
# per-query-cursor baseline (across backends and shard counts, device
# decode on) at <= 0.5x read bytes, and N concurrent identical queries
# must read < 2x the bytes of one query — not Nx
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.search_speed \
  --hot-traffic 24 --scale 0.05
# tiny-corpus smoke of live per-shard update streams: interleaved
# update/search rounds must serve results identical to a from-scratch
# rebuild, with targeted (touched-key digest) invalidation dropping
# strictly fewer cache entries — and reading fewer bytes — than the
# whole-namespace baseline
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.update_speed \
  --scale 0.05 --queries 12 --parts 3 --shards 2
# tiny-corpus smoke of the replica serving tier: a 2-replica fabric
# must serve results element-wise identical to the single-reader path
# (across backends and shard counts, including one replica killed
# mid-batch by an injected fault, which must force a real failover)
# with routing that keeps the busiest replica's read bytes within 1.25x
# of the mean
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.search_speed \
  --replicas 2 --scale 0.05 --queries 12 --backend numpy
# tiny-corpus smoke of the durable on-disk backend: the WAL-fed store
# must charge the simulated devices exactly like the in-memory
# substrate, recover to element-wise identical results (replay and
# checkpoint paths), and fold streams without ever reading more bytes
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.durability \
  --scale 0.05 --queries 12 --parts 3 --shards 2
# dev mode + DeprecationWarning-as-error: deprecations surface as
# failures here, not as breakage on the next interpreter upgrade
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" exec python -X dev \
  -W error::DeprecationWarning -m pytest -x -q "$@"
