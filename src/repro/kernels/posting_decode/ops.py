"""Dispatch wrappers: backend-selected varint posting decode.

``unpack_varints`` runs step 3 of the byte-parallel decode (see
``ref.py``) on the chosen backend; ``DeviceDecoder`` wraps it behind
the exact ``feed``/state surface of the host
:class:`~repro.core.postings.PostingDecoder`, so the lazy cursor path
can swap decoders without changing semantics; ``decode_member_prefilter``
is the fused decode→intersect entry point (decode a chunk AND mask its
rows against another list's doc ids in one call).

Device-width gate: jax runs with 64-bit disabled, so the jax/pallas
paths are taken only when every varint in the block fits 4 bytes (28
payload bits < int32).  Wider varints fall back to the exact int64 host
path — callers never see a difference (the parity suite in
``tests/test_kernels.py`` pins this bit-for-bit), and a
:class:`~repro.kernels.DeviceCounts` passed in records the fallback.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import DeviceCounts, interpret_mode
from repro.kernels.intersect.ops import doc_member_mask
from repro.kernels.posting_decode.kernel import varint_unpack_kernel
from repro.kernels.posting_decode.ref import (
    as_byte_array,
    byte_prep,
    complete_prefix,
    expand_deltas,
    unpack_varints_np,
)

DECODE_BACKENDS = ("numpy", "jax", "pallas")

# widest varint the device integer can hold: 4 bytes = 28 payload bits
_MAX_DEVICE_VARINT_BYTES = 4

# blocks below this take the segment_sum path even under the pallas
# backend: kernel dispatch (and interpret-mode tracing on CPU) dominates
# tiny launches; the dense-tile kernel earns its keep on big blocks
_PALLAS_MIN_BYTES = 1 << 14


def _pow2(n: int, floor: int = 8) -> int:
    return max(floor, 1 << int(np.ceil(np.log2(max(n, 1)))))


@functools.partial(jax.jit, static_argnums=2)
def _segment_sum_jit(contrib, vid, num_segments: int):
    return jax.ops.segment_sum(contrib, vid, num_segments=num_segments)


def unpack_varints(buf, backend: str = "numpy",
                   counts: Optional[DeviceCounts] = None) -> np.ndarray:
    """Decode a terminator-aligned byte buffer's varints as (N,) int64.

    ``backend`` picks where the segmented sum runs; the byte prep (flag
    scan, ranks, shifts) is host work either way.  Blocks containing a
    varint wider than the int32 gate run the host path regardless — the
    result is always exact int64.  ``counts`` records that fallback and
    every kernel launch.
    """
    if backend not in DECODE_BACKENDS:
        raise ValueError(
            f"unknown decode backend {backend!r}; expected one of "
            f"{DECODE_BACKENDS}"
        )
    buf = as_byte_array(buf)
    if backend == "numpy" or buf.size == 0:
        return unpack_varints_np(buf)
    contrib, vid, n_vals = byte_prep(buf)
    widths = np.bincount(vid, minlength=n_vals)
    if widths.max(initial=0) > _MAX_DEVICE_VARINT_BYTES:
        if counts is not None:
            counts.fallback("varint_width")
        return unpack_varints_np(buf)
    if backend == "jax":
        # pad bytes AND segments to power-of-two buckets: chunk payloads
        # vary byte by byte, and an unpadded call would retrace the jit
        # per distinct (M, n_vals) pair — pow2 bucketing caps the number
        # of compiled shapes at a handful per stream
        M2 = _pow2(contrib.size)
        n2 = _pow2(n_vals + 1)  # sentinel id n_vals stays in range
        vid_p = np.concatenate(
            [vid, np.full(M2 - contrib.size, n_vals, dtype=np.int64)]
        )
        contrib_p = np.concatenate(
            [contrib, np.zeros(M2 - contrib.size, dtype=np.int64)]
        )
        values = _segment_sum_jit(
            jnp.asarray(contrib_p, jnp.int32),
            jnp.asarray(vid_p, jnp.int32),
            n2,
        )
        return np.asarray(values[:n_vals]).astype(np.int64)
    # pallas: pad bytes with a sentinel id beyond every output slot and
    # values to the block grid; sentinel bytes can never hit a slot.
    # Each 1-D block is the whole padded array or a multiple of 1024:
    # XLA tiles longer int32 vectors T(1024), and Mosaic refuses a block
    # that does not match that tiling
    M = int(contrib.size)
    bn = min(1024, _pow2(n_vals))
    bm = min(1024, _pow2(M))
    n_pad = (-n_vals) % bn
    m_pad = (-M) % bm
    vid_p = np.concatenate(
        [vid, np.full(m_pad, n_vals + n_pad, dtype=np.int64)]
    )
    contrib_p = np.concatenate([contrib, np.zeros(m_pad, dtype=np.int64)])
    interpret = interpret_mode()
    values = varint_unpack_kernel(
        jnp.asarray(vid_p, jnp.int32),
        jnp.asarray(contrib_p, jnp.int32),
        n_vals + n_pad,
        bn=bn,
        bm=bm,
        interpret=interpret,
    )
    if counts is not None:
        counts.launch("varint_unpack", interpret)
    return np.asarray(values[:n_vals]).astype(np.int64)


class DeviceDecoder:
    """Incremental posting decoder with a device-resident varint unpack.

    Drop-in for :class:`repro.core.postings.PostingDecoder` on the
    untagged streams the lazy (K_OWN) cursor path feeds: same ``feed``
    contract (decode every complete record of ``rem + data``, buffer the
    tail), same ``state()``/``set_state()`` carry tuple — a stream may
    be suspended under one decoder and resumed under the other.  The
    delta expansion stays exact host int64; only the byte-crunching
    varint unpack is dispatched to the device; ``counts`` records its
    launches and host fallbacks.
    """

    def __init__(self, backend: str = "jax",
                 counts: Optional[DeviceCounts] = None):
        if backend not in DECODE_BACKENDS:
            raise ValueError(
                f"unknown decode backend {backend!r}; expected one of "
                f"{DECODE_BACKENDS}"
            )
        self.backend = backend
        self.counts = counts
        self._rem = b""
        self._prev_doc = 0
        self._prev_pos = 0
        self._any = False

    @property
    def pending_bytes(self) -> int:
        return len(self._rem)

    def feed(self, data) -> Tuple[np.ndarray, np.ndarray]:
        buf = self._rem + bytes(data)
        cut = complete_prefix(np.frombuffer(buf, dtype=np.uint8))
        backend = self.backend
        if backend == "pallas" and cut < _PALLAS_MIN_BYTES:
            backend = "jax"
        values = unpack_varints(buf[:cut], backend=backend,
                                counts=self.counts)
        posts, (pd, pp, st) = expand_deltas(
            values, self._prev_doc, self._prev_pos, self._any
        )
        self._rem = buf[cut:]
        self._prev_doc, self._prev_pos, self._any = pd, pp, st
        return posts, np.zeros(posts.shape[0], dtype=np.int64)

    # carry tuple shared with PostingDecoder (see its state/set_state)
    def state(self) -> Tuple[bytes, int, int, bool]:
        return (self._rem, self._prev_doc, self._prev_pos, self._any)

    def set_state(self, state: Tuple[bytes, int, int, bool]) -> None:
        rem, prev_doc, prev_pos, any_ = state
        self._rem = bytes(rem)
        self._prev_doc = int(prev_doc)
        self._prev_pos = int(prev_pos)
        self._any = bool(any_)


def decode_member_prefilter(
    data,
    other_docs: np.ndarray,
    backend: str = "pallas",
    state: Tuple[bytes, int, int, bool] = (b"", 0, 0, False),
) -> Tuple[np.ndarray, np.ndarray, Tuple[bytes, int, int, bool]]:
    """Fused decode→intersect: decode a posting chunk and mask its rows
    whose doc id occurs in ``other_docs`` — one entry point instead of a
    host decode followed by a separate membership pass, so a hot chunk's
    bytes go straight from storage to the intersect prefilter.

    ``state`` is the decoder carry (``DeviceDecoder.state()`` tuple) so
    chunked streams fuse too.  Returns ``(posts, member_mask,
    new_state)``; the mask is exact (the pallas path falls back to the
    searchsorted host test when doc ids exceed the kernel's int32 key
    width).
    """
    dec = DeviceDecoder(
        backend=backend if backend in DECODE_BACKENDS else "numpy"
    )
    dec.set_state(state)
    posts, _ = dec.feed(data)
    docs = posts[:, 0]
    other = np.unique(np.asarray(other_docs, dtype=np.int64))
    mask = None
    if backend == "pallas":
        mask = doc_member_mask(docs, other)
    if mask is None:
        if other.size == 0 or docs.size == 0:
            mask = np.zeros(docs.shape, dtype=bool)
        else:
            idx = np.clip(np.searchsorted(other, docs), 0, other.size - 1)
            mask = other[idx] == docs
    return posts, np.asarray(mask, dtype=bool), dec.state()


# ------------------------------------------------- device-resident rows ---
def to_device_rows(posts: np.ndarray) -> Optional[jnp.ndarray]:
    """(N,2) int64 postings → int32 device buffer, or None when any
    value exceeds the device integer width (jax runs without 64-bit, so
    an int64 upload would silently truncate — the gate keeps the device
    tier exact-or-absent)."""
    if posts.size and int(posts.max()) >= np.iinfo(np.int32).max:
        return None
    return jnp.asarray(posts, jnp.int32)


def from_device_rows(buf: jnp.ndarray) -> np.ndarray:
    """Device buffer → immutable (N,2) int64 host rows (the cursor ABI)."""
    rows = np.asarray(buf).astype(np.int64)
    rows.flags.writeable = False
    return rows
