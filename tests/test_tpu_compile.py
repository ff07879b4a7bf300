"""The search path's device programs compile for a TPU v5e chip.

Nothing runs here: each program is lowered and compiled for one chip of a
described (not attached) ``v5e:2x2`` topology, which raises whatever the
chip's compiler would refuse — a Pallas block that does not match XLA's
tiling, too much fast memory — at no chip time.  The kernels are compiled
at the exact shapes their dispatchers produce: the test calls the
dispatcher, captures the arguments it hands the kernel, and compiles the
kernel on those.

The topology is described inside a fixture (never at import), because
only one process may load the TPU library and every test worker imports
every test file.
"""

import os

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

import repro.kernels.intersect.ops as intersect_ops
import repro.kernels.posting_decode.ops as decode_ops
from repro.core.postings import encode_varint
from repro.search.join import batched_window_mask
from repro.search.scoring import _jitted_score


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


class _Captured(Exception):
    pass


def _capture(monkeypatch, module, name, call):
    """Run ``call``; return the (args, kwargs) it passes ``module.name``,
    stopping it there (nothing is computed on the host)."""
    seen = {}

    def record(*args, **kw):
        seen["call"] = (args, kw)
        raise _Captured

    monkeypatch.setattr(module, name, record)
    with pytest.raises(_Captured):
        call()
    return seen["call"]


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)


def _varint_buf(n, seed):
    """``n`` varints of 1-3 bytes, as posting deltas are."""
    rng = np.random.RandomState(seed)
    buf = bytearray()
    for v in rng.randint(0, 1 << 21, n):
        encode_varint(int(v), buf)
    return bytes(buf)


@pytest.mark.parametrize("n_values", [64, 300, 1000, 4096, 8192])
def test_varint_unpack_kernel_compiles(one_chip, monkeypatch, n_values):
    buf = _varint_buf(n_values, seed=n_values)
    (vid, contrib, n_total), kw = _capture(
        monkeypatch, decode_ops, "varint_unpack_kernel",
        lambda: decode_ops.unpack_varints(buf, backend="pallas"),
    )
    from repro.kernels.posting_decode.kernel import varint_unpack_kernel

    fn = jax.jit(lambda v, c: varint_unpack_kernel(
        v, c, n_total, bn=kw["bn"], bm=kw["bm"], interpret=False))
    fn.lower(_spec(vid, one_chip), _spec(contrib, one_chip)).compile()


@pytest.mark.parametrize("na,nb", [
    (64, 64), (300, 5000), (2048, 256), (4096, 128), (1500, 3000),
    (8192, 8192),
])
def test_intersect_kernel_compiles(one_chip, monkeypatch, na, nb):
    rng = np.random.RandomState(na + nb)
    a = np.unique(rng.randint(0, 1 << 20, na)).astype(np.int32)
    b = np.unique(rng.randint(0, 1 << 20, nb)).astype(np.int32)
    (ap, bp), kw = _capture(
        monkeypatch, intersect_ops, "intersect_kernel",
        lambda: intersect_ops.intersect_sorted(a, b),
    )
    from repro.kernels.intersect.kernel import intersect_kernel

    fn = jax.jit(lambda x, y: intersect_kernel(
        x, y, bn=kw["bn"], bm=kw["bm"], interpret=False))
    fn.lower(_spec(ap, one_chip), _spec(bp, one_chip)).compile()


@pytest.mark.parametrize("nb,n,m", [(1, 64, 1024), (8, 4096, 4096),
                                    (64, 256, 8192)])
def test_batched_window_mask_bucket_compiles(one_chip, nb, n, m):
    i32 = np.dtype(np.int32)
    args = [jax.ShapeDtypeStruct(s, i32, sharding=one_chip)
            for s in ((nb, n), (nb, m), (nb,))]
    batched_window_mask.lower(*args).compile()


@pytest.mark.parametrize("slots,nb", [(1, 8), (3, 1024), (4, 8192)])
def test_ranked_score_compiles(one_chip, slots, nb):
    i32 = np.dtype(np.int32)
    _jitted_score(slots, nb, 4).lower(
        jax.ShapeDtypeStruct((slots, nb), i32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), i32, sharding=one_chip),
    ).compile()


def test_jax_decode_segment_sum_compiles(one_chip, monkeypatch):
    buf = _varint_buf(4096, seed=3)
    (contrib, vid, n2), _ = _capture(
        monkeypatch, decode_ops, "_segment_sum_jit",
        lambda: decode_ops.unpack_varints(buf, backend="jax"),
    )
    monkeypatch.undo()
    decode_ops._segment_sum_jit.lower(
        _spec(contrib, one_chip), _spec(vid, one_chip), n2
    ).compile()
