"""Varint unpack as a Pallas segmented-sum kernel (TPU adaptation).

The byte-parallel decode (see ``ref.py``) reduces LEB128 unpacking to a
segmented sum: byte ``k`` carries a shifted payload ``contrib[k]`` and a
SORTED segment id ``vid[k]`` (which varint it belongs to), and
``values[v] = sum(contrib[k] for vid[k] == v)``.  A scalar gather-scan
is pointer chasing; the TPU-native formulation is the same dense-tile
broadcast-compare as the intersect kernel: for each (value-block,
byte-block) pair, compare the block's value ids against the tile's
output slots and sum the masked contributions.  Sortedness of ``vid``
bounds useful work exactly like sorted doc ids do for intersect — tiles
whose id ranges don't overlap are skipped via the block-corner test.

Grid = (N/bn, M/bm), byte blocks innermost; the output value block
accumulates across byte blocks in place.  All int32: the dispatch layer
(``ops.py``) gates on varint width so no contribution or value can
overflow the device integer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(v_ref, c_ref, o_ref, *, bn: int, bm: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    vid = v_ref[...]      # (bm,) sorted per-byte value ids
    contrib = c_ref[...]  # (bm,) shifted payloads
    lo = pl.program_id(0) * bn
    # block-corner range test: sorted ids => disjoint ranges, no hits
    overlap = jnp.logical_and(vid[0] <= lo + bn - 1, vid[bm - 1] >= lo)

    @pl.when(overlap)
    def _tile():
        # (bn, bm) VPU tile: output slot ids vs byte segment ids
        slots = lo + jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 0)
        hit = slots == vid[None, :]
        o_ref[...] = o_ref[...] + jnp.where(
            hit, contrib[None, :], 0
        ).sum(axis=1).astype(o_ref.dtype)


def varint_unpack_kernel(
    vid: jnp.ndarray,      # (M,) sorted int32 segment ids
    contrib: jnp.ndarray,  # (M,) int32 shifted payloads
    n_values: int,         # N, a multiple of bn
    *,
    bn: int = 1024,
    bm: int = 1024,
    interpret: bool,
) -> jnp.ndarray:
    M = vid.shape[0]
    assert n_values % bn == 0 and M % bm == 0, (n_values, M, bn, bm)
    kern = functools.partial(_kernel, bn=bn, bm=bm)
    return pl.pallas_call(
        kern,
        grid=(n_values // bn, M // bm),
        in_specs=[
            pl.BlockSpec((bm,), lambda i, j: (j,)),
            pl.BlockSpec((bm,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_values,), jnp.int32),
        interpret=interpret,
    )(vid, contrib)
