"""Position-join backends for proximity search.

The window join is the query-side hot spot of the ordinary+join route:
given two posting lists sorted by (doc, pos), keep the rows of ``a``
that have a row of ``b`` in the same doc within ``window`` positions.

Three interchangeable backends:

  * ``numpy_window_join``   — host oracle (searchsorted over packed keys),
  * ``jax_window_join``     — jit-compiled, padded to powers of two; the
    batched variant ``batched_window_mask`` joins many (a, b) pairs of the
    same padded shape in ONE kernel launch (vmapped searchsorted),
  * ``pallas_window_join``  — doc-level prefilter through the Pallas
    ``intersect`` kernel (dense tile compare on TPU), then an exact host
    window join over the surviving rows.

Key packing is explicit everywhere: ``pos_scale`` picks the smallest
power of two that can hold ``max_pos + window + 1``, so ``doc * scale +
pos ± window`` never crosses a doc boundary, and the int32-vs-int64
decision is made from the *packed key range* — never from whatever dtype
``jnp.asarray`` happens to produce (without x64, JAX silently truncates
int64 inputs to int32, which used to flip the scale choice and corrupt
joins for doc ids beyond the 24-bit packing range).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

_INT32_SAFE = np.int64(np.iinfo(np.int32).max)


# ----------------------------------------------------------- key packing --
def pos_scale(max_pos: int, window: int) -> int:
    """Smallest power of two > max_pos + window (explicit, data-driven)."""
    need = int(max_pos) + int(window) + 1
    scale = 1
    while scale < need:
        scale <<= 1
    return scale


def pack_keys(
    a: np.ndarray, b: np.ndarray, window: int
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Pack (doc, pos) rows into sortable int64 scalar keys.

    Returns ``(akey, bkey, scale)`` with ``key = doc * scale + pos``;
    ``scale`` leaves headroom so ``key ± window`` stays inside the doc.
    """
    max_pos = int(max(a[:, 1].max(), b[:, 1].max())) if a.size and b.size else 0
    scale = pos_scale(max_pos, window)
    akey = a[:, 0] * np.int64(scale) + a[:, 1]
    bkey = b[:, 0] * np.int64(scale) + b[:, 1]
    return akey, bkey, scale


# ------------------------------------------------------------ numpy oracle --
def numpy_window_join(a: np.ndarray, b: np.ndarray, window: int) -> np.ndarray:
    """Rows of ``a`` having a row of ``b`` with the same doc and
    |pos_a - pos_b| <= window.  Both (N,2), sorted by (doc, pos)."""
    if a.size == 0 or b.size == 0:
        return np.zeros((0, 2), dtype=np.int64)
    akey, bkey, _ = pack_keys(a, b, window)
    lo = np.searchsorted(bkey, akey - window)
    hi = np.searchsorted(bkey, akey + window, side="right")
    return a[hi > lo]


def numpy_phrase_join(a: np.ndarray, b: np.ndarray, dist: int) -> np.ndarray:
    """Rows of ``a`` where ``b`` has the same doc at exactly pos_a + dist
    (ordered adjacency — the stop-sequence index semantics)."""
    if a.size == 0 or b.size == 0:
        return np.zeros((0, 2), dtype=np.int64)
    akey, bkey, _ = pack_keys(a, b, dist)
    want = akey + dist
    i = np.searchsorted(bkey, want)
    i = np.minimum(i, bkey.shape[0] - 1)
    return a[bkey[i] == want]


# ---------------------------------------------------------------- jax path --
@jax.jit
def _window_mask(akey: jnp.ndarray, bkey: jnp.ndarray, window: jnp.ndarray):
    lo = jnp.searchsorted(bkey, akey - window)
    hi = jnp.searchsorted(bkey, akey + window, side="right")
    return hi > lo


@jax.jit
def batched_window_mask(
    akeys: jnp.ndarray, bkeys: jnp.ndarray, windows: jnp.ndarray
) -> jnp.ndarray:
    """Join B pairs at once: (B,N) x (B,M) packed keys -> (B,N) bool mask.

    One compiled kernel per (B, N, M) shape; the executor buckets jobs into
    power-of-two shapes so the variant count stays tiny.
    """

    def one(ak, bk, w):
        lo = jnp.searchsorted(bk, ak - w)
        hi = jnp.searchsorted(bk, ak + w, side="right")
        return hi > lo

    return jax.vmap(one)(akeys, bkeys, windows)


def _jax_dtype_for(max_key: int, window: int) -> Optional[np.dtype]:
    """Pick the device dtype the packed keys survive in, or None."""
    if max_key + window < int(_INT32_SAFE):
        return np.int32
    if jax.config.jax_enable_x64:
        return np.int64
    return None  # keys do not fit the device integer width


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def jax_window_join(a: np.ndarray, b: np.ndarray, window: int) -> np.ndarray:
    """JAX path: pack keys host-side, pad to the next power of two, join.

    Falls back to the numpy oracle when the packed keys cannot be
    represented on the device (x64 disabled and keys beyond int32) — a
    silent wrong answer is never an option.
    """
    if a.size == 0 or b.size == 0:
        return np.zeros((0, 2), dtype=np.int64)
    akey, bkey, _ = pack_keys(a, b, window)
    dtype = _jax_dtype_for(int(max(akey[-1], bkey[-1])), window)
    if dtype is None:
        return numpy_window_join(a, b, window)

    def pad(key: np.ndarray, fill: int) -> np.ndarray:
        n = _pow2(key.shape[0])
        return np.concatenate(
            [key.astype(dtype), np.full((n - key.shape[0],), fill, dtype)]
        )

    big = np.iinfo(dtype).max
    # b pads ABOVE every real a-key + window (the dtype gate guarantees
    # real keys stay below big - window), so padding can never witness a
    # hit; a pads stay clear of +window overflow — their mask rows are
    # sliced away below
    pa = pad(akey, big - window - 1)
    pb = pad(bkey, big)
    mask = np.asarray(_window_mask(jnp.asarray(pa), jnp.asarray(pb),
                                   jnp.asarray(window, dtype)))
    return a[mask[: a.shape[0]]]


# --------------------------------------------------------- pallas backend --
def pallas_window_join(a: np.ndarray, b: np.ndarray, window: int,
                       counts=None) -> np.ndarray:
    """Doc-level prefilter with the Pallas intersect kernel, exact finish.

    The kernel computes membership of ``a``'s doc ids in ``b``'s doc ids
    (dense tile compare — the TPU-native formulation); only rows in common
    docs reach the exact host window join, which on real queries is a tiny
    fraction of the input.  ``counts`` (a
    :class:`~repro.kernels.DeviceCounts`) records the kernel launch or the
    host fallback.
    """
    if a.size == 0 or b.size == 0:
        return np.zeros((0, 2), dtype=np.int64)
    from repro.kernels.intersect.ops import doc_member_mask

    mask = doc_member_mask(a[:, 0], b[:, 0], counts=counts)
    if mask is None:  # doc ids beyond the kernel's int32 keys
        if counts is not None:
            counts.fallback("intersect_docs")
        return numpy_window_join(a, b, window)
    a_hit = a[mask]
    if a_hit.size == 0:
        return np.zeros((0, 2), dtype=np.int64)
    b_hit = b[np.isin(b[:, 0], np.unique(a_hit[:, 0]))]
    return numpy_window_join(a_hit, b_hit, window)


JOIN_BACKENDS = {
    "numpy": numpy_window_join,
    "jax": jax_window_join,
    "pallas": pallas_window_join,
}
