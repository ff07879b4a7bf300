"""Sorted-list intersection Pallas kernel (TPU adaptation).

This is the query-side hot spot of the paper: intersecting posting lists
(doc-id keys) during proximity search.  A CPU merge-intersection is
pointer chasing — hostile to the TPU's vector unit.  The TPU-native
formulation is dense tile comparison: for each (a-block, b-block) pair,
broadcast-compare the 2D tile and OR-reduce.  O(N*M/(bn*bm)) tiles of
pure VPU compares beats a data-dependent merge on this hardware, and the
sortedness still bounds useful work: tiles whose ranges don't overlap
contribute nothing and are skipped via a cheap range test on block
corners (the block-level analogue of galloping).

Grid = (N/bn, M/bm), b innermost; the output mask block accumulates
across b-blocks in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, o_ref, *, bn: int, bm: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]  # (bn,)
    b = b_ref[...]  # (bm,)
    # block-corner range test: sorted inputs => disjoint ranges, no hits
    overlap = jnp.logical_and(a[0] <= b[bm - 1], b[0] <= a[bn - 1])

    @pl.when(overlap)
    def _tile():
        eq = a[:, None] == b[None, :]           # (bn, bm) VPU compare tile
        o_ref[...] = jnp.logical_or(
            o_ref[...], eq.any(axis=1)
        ).astype(o_ref.dtype)


def intersect_kernel(
    a: jnp.ndarray,  # (N,) sorted int32
    b: jnp.ndarray,  # (M,) sorted int32
    *,
    bn: int = 1024,
    bm: int = 1024,
    interpret: bool,
) -> jnp.ndarray:
    N, M = a.shape[0], b.shape[0]
    assert N % bn == 0 and M % bm == 0, (N, M, bn, bm)
    kern = functools.partial(_kernel, bn=bn, bm=bm)
    return pl.pallas_call(
        kern,
        grid=(N // bn, M // bm),
        in_specs=[
            pl.BlockSpec((bn,), lambda i, j: (i,)),
            pl.BlockSpec((bm,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((N,), jnp.bool_),
        interpret=interpret,
    )(a, b)
