"""Pallas kernels vs their pure-jnp oracles: shape/dtype sweeps in
interpret mode (the kernel bodies execute in Python on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag.ops import embedding_bag_fixed
from repro.kernels.embedding_bag.ref import embedding_bag_fixed_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels import DeviceCounts, interpret_mode
from repro.kernels.intersect.ops import intersect_sorted
from repro.kernels.intersect.ref import intersect_sorted_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.core.postings import PostingDecoder, encode_postings, encode_varint
from repro.kernels.posting_decode.ops import (
    DECODE_BACKENDS,
    DeviceDecoder,
    decode_member_prefilter,
    from_device_rows,
    to_device_rows,
    unpack_varints,
)
from repro.kernels.posting_decode.ref import (
    as_byte_array,
    complete_prefix,
    decode_block_ref,
    unpack_varints_np,
)

RNG = np.random.RandomState(7)


@pytest.mark.parametrize(
    "B,H,S,D,bq,bk",
    [
        (1, 1, 64, 32, 32, 32),
        (2, 3, 128, 64, 64, 32),
        (1, 2, 256, 128, 128, 128),
        (2, 1, 128, 16, 128, 64),  # D not lane-sized: interpret-mode check
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, S, D, bq, bk, dtype):
    q = jnp.asarray(RNG.randn(B, H, S, D), dtype)
    k = jnp.asarray(RNG.randn(B, H, S, D), dtype)
    v = jnp.asarray(RNG.randn(B, H, S, D), dtype)
    got = flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
    want = flash_attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert got.dtype == dtype
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < tol


def test_flash_attention_non_causal():
    q = jnp.asarray(RNG.randn(1, 2, 128, 32), jnp.float32)
    k = jnp.asarray(RNG.randn(1, 2, 128, 32), jnp.float32)
    v = jnp.asarray(RNG.randn(1, 2, 128, 32), jnp.float32)
    got = flash_attention(q, k, v, causal=False, bq=64, bk=64)
    want = flash_attention_ref(q, k, v, causal=False)
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize(
    "B,H,D,page,n_pages,max_pages",
    [(2, 4, 32, 16, 12, 4), (3, 8, 64, 8, 30, 7), (1, 2, 128, 32, 6, 3)],
)
def test_paged_attention(B, H, D, page, n_pages, max_pages):
    q = jnp.asarray(RNG.randn(B, H, D), jnp.float32)
    kp = jnp.asarray(RNG.randn(n_pages, page, D), jnp.float32)
    vp = jnp.asarray(RNG.randn(n_pages, page, D), jnp.float32)
    bt = jnp.asarray(
        RNG.choice(n_pages, size=(B, max_pages)), jnp.int32
    )
    lens = jnp.asarray(
        RNG.randint(1, max_pages * page + 1, size=B), jnp.int32
    )
    got = paged_attention(q, kp, vp, bt, lens)
    want = paged_attention_ref(q, kp, vp, bt, lens)
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_paged_attention_chain_limit_semantics():
    """max_pages bounds the indirections per read — the CH chain-limit
    invariant carried onto the device (paper 5.7.3)."""
    B, H, D, page = 2, 2, 32, 16
    for max_pages in (2, 5, 9):
        n_pages = max_pages * B
        q = jnp.asarray(RNG.randn(B, H, D), jnp.float32)
        kp = jnp.asarray(RNG.randn(n_pages, page, D), jnp.float32)
        vp = jnp.asarray(RNG.randn(n_pages, page, D), jnp.float32)
        bt = jnp.asarray(
            np.arange(B * max_pages).reshape(B, max_pages), jnp.int32
        )
        lens = jnp.full((B,), max_pages * page, jnp.int32)
        got = paged_attention(q, kp, vp, bt, lens)
        want = paged_attention_ref(q, kp, vp, bt, lens)
        assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("V,D,B,K", [(64, 32, 4, 3), (256, 128, 16, 8),
                                     (1000, 64, 7, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag(V, D, B, K, dtype):
    tb = jnp.asarray(RNG.randn(V, D), dtype)
    ids = jnp.asarray(RNG.randint(0, V, (B, K)), jnp.int32)
    w = jnp.asarray(RNG.rand(B, K), jnp.float32)
    got = embedding_bag_fixed(tb, ids, w)
    want = embedding_bag_fixed_ref(tb, ids, w)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < tol


@pytest.mark.parametrize("na,nb,bn,bm", [
    (100, 200, 32, 64), (1000, 50, 256, 32), (8, 8, 8, 8),
    (2000, 3000, 1024, 1024),
])
def test_intersect(na, nb, bn, bm):
    a = np.unique(RNG.randint(0, 10_000, na)).astype(np.int32)
    b = np.unique(RNG.randint(0, 10_000, nb)).astype(np.int32)
    got = np.asarray(intersect_sorted(a, b, bn=bn, bm=bm))
    want = np.asarray(intersect_sorted_ref(jnp.asarray(a), jnp.asarray(b)))
    assert (got == want).all()


def test_intersect_disjoint_and_identical():
    a = np.arange(0, 100, dtype=np.int32)
    b = np.arange(1000, 1100, dtype=np.int32)
    assert not np.asarray(intersect_sorted(a, b)).any()
    assert np.asarray(intersect_sorted(a, a)).all()


@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None),
])
def test_interpret_mode_only_on_cpu(monkeypatch, platform, interpret):
    """The one interpret decision: interpreted on cpu, compiled on tpu,
    and no quiet interpretation anywhere else."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError):
            interpret_mode()
    else:
        assert interpret_mode() is interpret


# ----------------------------------------------- posting decode parity --
def _posting_stream(n, seed, max_doc=50, max_pos=200_000):
    rng = np.random.RandomState(seed)
    arr = np.stack(
        [np.sort(rng.randint(0, max_doc, n)), rng.randint(0, max_pos, n)], 1
    ).astype(np.int64)
    arr = arr[np.lexsort((arr[:, 1], arr[:, 0]))]
    return arr, encode_postings(arr)


def _varint_buf(values):
    buf = bytearray()
    for v in values:
        encode_varint(int(v), buf)
    return bytes(buf)


@pytest.mark.parametrize("backend", DECODE_BACKENDS)
def test_unpack_varints_backend_parity(backend):
    """unpack_varints agrees bit-for-bit with the host oracle on every
    backend, across widths 1..5 bytes — the 5-byte sweep exceeds the
    int32 device gate, so jax/pallas must take the exact fallback."""
    rng = np.random.RandomState(21)
    for width in (1, 2, 3, 4, 5):
        vals = rng.randint(
            0, 1 << (7 * width), size=rng.randint(1, 400)
        ).astype(np.int64)
        buf = _varint_buf(vals)
        got = unpack_varints(buf, backend=backend)
        want = unpack_varints_np(as_byte_array(buf))
        assert got.dtype == np.int64
        assert (got == want).all() and (want == vals).all()


@pytest.mark.parametrize("backend", DECODE_BACKENDS)
def test_unpack_varints_wide_values_exact(backend):
    """Values past 28 payload bits (up to near 2^63) stay exact — the
    device paths detect the wide varint and defer to host int64."""
    wide = [3, 1 << 40, 127, (1 << 62) - 5, 0, 1 << 28]
    counts = DeviceCounts()
    got = unpack_varints(_varint_buf(wide), backend=backend, counts=counts)
    assert got.tolist() == wide
    # the device paths record the host fallback; numpy never leaves it
    want = {} if backend == "numpy" else {"varint_width": 1}
    assert dict(counts.fallbacks) == want


def test_unpack_varints_unknown_backend_rejected():
    with pytest.raises(ValueError):
        unpack_varints(b"\x01", backend="cuda")
    with pytest.raises(ValueError):
        DeviceDecoder(backend="cuda")


@pytest.mark.parametrize("backend", DECODE_BACKENDS)
def test_device_decoder_matches_host_under_random_chunkings(backend):
    """DeviceDecoder == PostingDecoder bit-for-bit on the same stream fed
    through random chunk boundaries (including cuts inside varints), and
    their carry states stay interchangeable throughout."""
    arr, enc = _posting_stream(300, seed=31)
    rng = np.random.RandomState(hash(backend) % (1 << 31))
    raw = np.frombuffer(enc, np.uint8)
    for _ in range(4):
        cuts = np.sort(
            rng.choice(len(enc), size=rng.randint(0, 12), replace=False)
        )
        host, dev = PostingDecoder(), DeviceDecoder(backend=backend)
        hrows, drows = [], []
        for c in np.split(raw, cuts):
            hrows.append(host.feed(c.tobytes())[0])
            drows.append(dev.feed(c.tobytes())[0])
            assert host.state() == dev.state()
        h = np.concatenate(hrows)
        assert (h == np.concatenate(drows)).all()
        assert (h == arr).all()


def test_decoder_suspend_under_one_resume_under_other():
    """The carry tuple is decoder-portable: suspend a stream under the
    host decoder and resume under the device one (and vice versa) —
    the contract that lets cached partials be replayed by either."""
    arr, enc = _posting_stream(200, seed=37)
    cut = len(enc) // 2
    host = PostingDecoder()
    head = host.feed(enc[:cut])[0]
    dev = DeviceDecoder(backend="jax")
    dev.set_state(host.state())
    tail = dev.feed(enc[cut:])[0]
    assert (np.concatenate([head, tail]) == arr).all()
    dev2 = DeviceDecoder(backend="jax")
    head2 = dev2.feed(enc[:cut])[0]
    host2 = PostingDecoder()
    host2.set_state(dev2.state())
    tail2 = host2.feed(enc[cut:])[0]
    assert (np.concatenate([head2, tail2]) == arr).all()


def test_decode_block_ref_matches_scalar_decoder():
    """The byte-parallel oracle (terminator scan → segmented sum → delta
    expansion) reproduces the scalar walk exactly, carry included."""
    arr, enc = _posting_stream(150, seed=41)
    cut = complete_prefix(as_byte_array(enc))
    assert cut == len(enc)  # encode ends on a record boundary
    mid = complete_prefix(as_byte_array(enc[: len(enc) // 2]))
    rows, carry = decode_block_ref(as_byte_array(enc[:mid]))
    host = PostingDecoder()
    want, _ = host.feed(enc[:mid])
    assert (rows == want).all()
    assert carry == host.state()[1:]
    rows2, carry2 = decode_block_ref(as_byte_array(enc[mid:]), *carry)
    assert (np.concatenate([rows, rows2]) == arr).all()
    assert carry2[2] is True


def test_pallas_routing_big_block_parity():
    """A feed past the pallas size gate actually launches the dense-tile
    kernel (interpret mode here); the rows must still equal the scalar
    decoder's bit-for-bit."""
    from repro.kernels.posting_decode.ops import _PALLAS_MIN_BYTES

    arr, enc = _posting_stream(
        4200, seed=43, max_doc=2000, max_pos=(1 << 27) - 1
    )
    assert len(enc) >= _PALLAS_MIN_BYTES
    dev = DeviceDecoder(backend="pallas")
    rows, _ = dev.feed(enc)
    want, _ = PostingDecoder().feed(enc)
    assert (rows == want).all()
    assert (rows == arr).all()


@pytest.mark.parametrize("backend", DECODE_BACKENDS)
def test_decode_member_prefilter_matches_separate_passes(backend):
    """The fused decode→intersect entry point returns exactly (host
    decode, membership test) on every backend, across chunked feeds."""
    arr, enc = _posting_stream(250, seed=53)
    docs = np.unique(arr[:, 0])
    other = np.concatenate([docs[::2], docs.max() + 7 + docs[:5]])
    state = (b"", 0, 0, False)
    posts_parts, mask_parts = [], []
    cut = len(enc) // 3
    for blob in (enc[:cut], enc[cut:]):
        posts, mask, state = decode_member_prefilter(
            blob, other, backend=backend, state=state
        )
        posts_parts.append(posts)
        mask_parts.append(mask)
    posts = np.concatenate(posts_parts)
    mask = np.concatenate(mask_parts)
    want, _ = PostingDecoder().feed(enc)
    assert (posts == want).all() and (posts == arr).all()
    assert (mask == np.isin(posts[:, 0], other)).all()
    assert state[0] == b""  # stream fully drained


def test_device_rows_roundtrip_and_width_gate():
    arr, _ = _posting_stream(100, seed=59)
    buf = to_device_rows(arr)
    back = from_device_rows(buf)
    assert back.dtype == np.int64
    assert (back == arr).all()
    assert not back.flags.writeable
    # values at/over int32 never reach the device tier (silent
    # truncation would corrupt — the gate returns None instead)
    big = np.array([[0, np.iinfo(np.int32).max]], dtype=np.int64)
    assert to_device_rows(big) is None
    empty = np.zeros((0, 2), dtype=np.int64)
    assert (from_device_rows(to_device_rows(empty)) == empty).all()
