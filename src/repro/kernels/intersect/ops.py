"""Dispatch wrappers: pad to block multiples, run the intersect kernel."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.kernels import DeviceCounts, interpret_mode
from repro.kernels.intersect.kernel import intersect_kernel


def intersect_sorted(a, b, bn: int = 1024, bm: int = 1024,
                     counts: Optional[DeviceCounts] = None):
    """mask[i] = a[i] in b for sorted int32 arrays (host-callable; pads to
    block multiples with sentinels that can never match).  ``counts``
    records the launch."""
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    N, M = a.shape[0], b.shape[0]
    bn = min(bn, max(8, 1 << int(np.ceil(np.log2(max(N, 1))))))
    bm = min(bm, max(8, 1 << int(np.ceil(np.log2(max(M, 1))))))
    pn = (-N) % bn
    pm = (-M) % bm
    big = jnp.iinfo(jnp.int32).max
    ap = jnp.concatenate([a, jnp.full((pn,), big - 1, a.dtype)])
    bp = jnp.concatenate([b, jnp.full((pm,), big, b.dtype)])
    interpret = interpret_mode()
    mask = intersect_kernel(ap, bp, bn=bn, bm=bm, interpret=interpret)
    if counts is not None:
        counts.launch("intersect", interpret)
    return mask[:N]


def doc_member_mask(
    a_docs: np.ndarray, b_docs: np.ndarray,
    counts: Optional[DeviceCounts] = None,
) -> Optional[np.ndarray]:
    """Host mask[i] = a_docs[i] occurs in b_docs, via the Pallas kernel.

    The doc-level prefilter of the proximity search pallas backend
    (``repro.search.join.pallas_window_join``).  ``a_docs`` must be sorted;
    ``b_docs`` is deduplicated here.  Returns None when the doc ids do not
    fit the kernel's int32 key width — callers fall back to a host join
    (and record it as the ``intersect_docs`` fallback).
    """
    if a_docs.size == 0 or b_docs.size == 0:
        return np.zeros(a_docs.shape, dtype=bool)
    b_docs = np.unique(b_docs)
    if int(a_docs[-1]) >= np.iinfo(np.int32).max or (
        int(b_docs[-1]) >= np.iinfo(np.int32).max
    ):
        return None
    mask = intersect_sorted(a_docs.astype(np.int32), b_docs.astype(np.int32),
                            counts=counts)
    return np.asarray(mask).astype(bool)
