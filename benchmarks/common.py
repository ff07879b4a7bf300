"""Shared corpus/world construction for the paper-reproduction benchmarks."""

from __future__ import annotations

import dataclasses
import os
import time
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

from repro.core.lexicon import Lexicon, make_lexicon
from repro.core.strategies import StrategyConfig
from repro.core.text_index import IndexSetConfig, TextIndexSet


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``
    — a fixed path, because the path is part of the cache key, so a
    directory that moves between runs never hits.  Call it from a
    program's ``main`` before the first compile; never at import and
    never in tests.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass
class World:
    lexicon: Lexicon
    parts: List[Tuple[np.ndarray, np.ndarray]]  # (tokens, offsets) per part
    doc_starts: List[int]

    @property
    def total_tokens(self) -> int:
        return sum(t.shape[0] for t, _ in self.parts)


def make_world(scale: float = 1.0, seed: int = 0, n_parts: int = 2) -> World:
    """Multi-part collection (paper 6.4: build part 1, update in place with
    the following parts; the paper's headline experiment uses two parts).

    scale=1 is CI-size (~0.8M tokens).  The paper's 71.5 GB collection is
    roughly scale=12000; I/O *ratios* between strategy sets are the
    reproduced quantity at any scale.
    """
    lex = make_lexicon(
        n_words=60_000,
        n_lemmas=26_000,
        n_stop=70,
        n_frequent=1_000,
        seed=1234 + seed,
    )
    n_docs = max(40, int(1200 * scale))
    parts = []
    doc_starts = []
    doc0 = 0
    for p in range(n_parts):
        toks, offs = generate_cached(lex, n_docs, 350, doc0, seed=100 + p)
        parts.append((toks, offs))
        doc_starts.append(doc0)
        doc0 += n_docs
    return World(lexicon=lex, parts=parts, doc_starts=doc_starts)


# hot-regime index geometry for the top-k early-termination bench AND the
# tier-1 effectiveness regression (tests/test_topk.py): small clusters and
# EM limit push the hot keys' lists into multi-chunk stream storage even at
# CI corpus sizes — the ONE definition both consumers share, so tuning the
# regime can never silently leave the other un-tuned
HOT_GEOMETRY = dict(cluster_size=256, em_limit=8, tag_extract_bytes=512)


def make_hot_world(scale: float = 1.0, seed: int = 0, n_parts: int = 2) -> World:
    """A *hot-vocabulary* collection for the top-k early-termination bench:
    a tiny lexicon makes every k-word tuple recur across many documents, so
    multi-component keys carry long stream-backed posting lists — the
    regime where a best-k search can stop far before the lists end.  (The
    standard :func:`make_world` vocabulary is so large that phrase keys
    rarely repeat, which leaves nothing for early termination to skip.)"""
    lex = make_lexicon(
        n_words=8, n_lemmas=5, n_stop=1, n_frequent=2,
        unknown_fraction=0.15, seed=7 + seed,
    )
    n_docs = max(80, int(800 * scale))
    parts = []
    doc_starts = []
    doc0 = 0
    for p in range(n_parts):
        toks, offs = generate_cached(lex, n_docs, 250, doc0, seed=300 + p)
        parts.append((toks, offs))
        doc_starts.append(doc0)
        doc0 += n_docs
    return World(lexicon=lex, parts=parts, doc_starts=doc_starts)


_GEN_CACHE: Dict[tuple, Tuple[np.ndarray, np.ndarray]] = {}


def generate_cached(lex, n_docs, avg_len, doc0, seed):
    from repro.data.corpus import generate_part

    key = (id(lex), n_docs, avg_len, doc0, seed)
    if key not in _GEN_CACHE:
        _GEN_CACHE[key] = generate_part(lex, n_docs, avg_len, doc0, seed)
    return _GEN_CACHE[key]


def bench_index_config(
    setname: str,
    cluster_size: int = 1024,
    build_ordinary_all: bool = False,
    fl_area_clusters: int = 4096,
    multi_k=3,
    **strategy_kw,
) -> IndexSetConfig:
    """Benchmark geometry: the CI corpus is ~10^4x smaller than the paper's
    71.5 GB, so the cluster geometry is scaled to keep the *postings-per-key
    vs cluster-size* regime comparable (1 KB clusters, 16 B EM limit, 64 B
    SR blocks, 2 KB TAG extraction).  All ratios between strategy sets are
    geometry-consistent with the paper's 32 KB/64 B/128 B/8 KB settings.

    The ONE config builder for sharded and unsharded benchmark substrates:
    benches that compare the two (``search_speed --shards``) rely on both
    being constructed from an identical ``IndexSetConfig``."""
    strategy_kw.setdefault("em_limit", 16)
    strategy_kw.setdefault("sr_block", 64)
    strategy_kw.setdefault("tag_extract_bytes", 2048)
    strategy = getattr(StrategyConfig, setname)(
        cluster_size=cluster_size, **strategy_kw
    )
    return IndexSetConfig(
        strategy=strategy,
        build_ordinary_all=build_ordinary_all,
        fl_area_clusters=fl_area_clusters,
        multi_k=multi_k,
    )


def build_index_set(world: World, setname: str, **cfg_kw) -> TextIndexSet:
    ts = TextIndexSet(bench_index_config(setname, **cfg_kw), world.lexicon,
                      seed=0)
    for (toks, offs), doc0 in zip(world.parts, world.doc_starts):
        ts.add_documents(toks, offs, doc0)
    return ts


def build_sharded_index_set(world: World, setname: str, n_shards: int,
                            **cfg_kw):
    """Identical :func:`bench_index_config` geometry as
    :func:`build_index_set`, partitioned by doc hash across ``n_shards``
    full per-shard substrates."""
    from repro.core.sharded_set import ShardedTextIndexSet

    sts = ShardedTextIndexSet(
        bench_index_config(setname, **cfg_kw), world.lexicon,
        n_shards=n_shards, seed=0,
    )
    for (toks, offs), doc0 in zip(world.parts, world.doc_starts):
        sts.add_documents(toks, offs, doc0)
    return sts


def timeit(fn, *args, repeats: int = 3, **kw) -> Tuple[float, object]:
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6, out  # microseconds
