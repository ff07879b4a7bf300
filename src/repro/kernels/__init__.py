"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel directory has:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — dispatch wrapper (interpret mode decided by
              :func:`interpret_mode`)
  ref.py    — pure-jnp oracle, used by the models and the tests

Kernels:
  flash_attention — causal online-softmax attention (train/prefill)
  paged_attention — decode attention through a block table whose depth is
                    bounded by the paper's chain-length limit (CH strategy)
  embedding_bag   — fused gather + segment-reduce (recsys hot path)
  intersect       — sorted posting-list intersection as dense VPU tiles
                    (TPU adaptation of merge-intersection: no pointer
                    chasing, block-parallel compares)
  posting_decode  — byte-parallel LEB128 varint posting decode (terminator
                    scan → segmented sum → host delta expansion); wraps a
                    DeviceDecoder drop-in for the scalar PostingDecoder
                    plus the fused decode→intersect prefilter entry point

The search path's kernels (intersect, posting_decode) report what they
did into a :class:`DeviceCounts` the caller passes: every launch, and
every exact host fallback taken because a value did not fit the device's
int32 integers.  The jax window join (``repro.search.join``) reports its
launches there too.
"""

from __future__ import annotations

import collections
from typing import Dict

import jax

# the search path's device programs: its Pallas kernels, which run
# compiled or interpreted, and the jax backend's window join
# (``batched_window_mask``, one launch per bucket: three host-to-device
# transfers and one back), which XLA always compiles; and the places
# where it may answer on the host instead of the device (each exact;
# see DeviceCounts)
PALLAS_KERNELS = ("intersect", "varint_unpack")
SEARCH_KERNELS = PALLAS_KERNELS + ("window_mask",)
FALLBACK_SITES = (
    "join_keys",       # packed (doc, pos) join keys beyond int32
    "intersect_docs",  # doc ids beyond the intersect kernel's int32 keys
    "varint_width",    # a varint wider than 4 bytes in a decode block
    "device_rows",     # a drained list the int32 device tier refused
)


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: the ONE decision.

    Interpreted on the ``cpu`` platform (where the tests run), compiled on
    ``tpu``.  Any other platform raises instead of quietly interpreting:
    a TPU run whose backend failed to start must not turn into a slow
    host run that looks like a device one.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels target the TPU; platform {platform!r} has "
        f"neither a compiled nor an interpreted mode here"
    )


class DeviceCounts:
    """What the device path did in one unit of work (a search batch).

    ``launch`` records a launch of a program in :data:`SEARCH_KERNELS`,
    split by whether it ran compiled or interpreted; ``fallback`` records
    an exact host answer taken at one of :data:`FALLBACK_SITES`.  Every
    count is made on the thread that runs the batch's join and streaming
    stages.
    """

    def __init__(self) -> None:
        self.compiled: Dict[str, int] = collections.Counter()
        self.interpreted: Dict[str, int] = collections.Counter()
        self.fallbacks: Dict[str, int] = collections.Counter()

    def launch(self, kernel: str, interpret: bool) -> None:
        (self.interpreted if interpret else self.compiled)[kernel] += 1

    def fallback(self, site: str, n: int = 1) -> None:
        if site not in FALLBACK_SITES:
            raise ValueError(f"unknown host-fallback site {site!r}")
        self.fallbacks[site] += n

    def as_trace(self) -> Dict[str, Dict[str, int]]:
        """Every kernel and every site, zeros included."""
        return {
            "compiled_launches": {k: self.compiled[k] for k in SEARCH_KERNELS},
            "interpreted_launches": {
                k: self.interpreted[k] for k in SEARCH_KERNELS
            },
            "host_fallbacks": {s: self.fallbacks[s] for s in FALLBACK_SITES},
        }
