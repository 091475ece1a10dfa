"""The npz decoder of ``columnar.deserialize`` against ``np.load``: equal
bytes, dtypes, shapes and column order with one member requested (the
caller's thread) and several (the pool), stored, 2-D and Fortran-ordered
members, the errors ``np.load`` raises, integrity checks, the per-path
member counts, and no span opened off the caller's thread."""
import io
import struct
import zipfile
import zlib

import numpy as np
import pytest

from repro.core import spans
from repro.engine import columnar
from repro.engine.columnar import ColumnBatch

# Every dtype the TPC-H configuration stores.
TPCH_DTYPES = ["int64", "int32", "float64", "int8", "S44", "S79"]
# Members requested from a two-column object: one is decoded in the
# caller's thread, several on the pool.
SIDES = {"serial": ["b"], "concurrent": ["a", "b"]}


def _random_column(rng, dtype, n):
    dtype = np.dtype(dtype)
    raw = rng.integers(0, 256, n * dtype.itemsize, dtype=np.uint8)
    return raw.view(dtype)


def _reference(data, names):
    with np.load(io.BytesIO(data)) as z:
        return {k: z[k] for k in (z.files if names is None else names)}


def _assert_same(out, ref):
    assert list(out) == list(ref)
    for k, want in ref.items():
        got = out[k]
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()   # bit-identical, NaNs too


def _decode(data, columns=None):
    before = dict(columnar.DECODE_STATS)
    out = columnar.deserialize(data, columns)
    return out, {k: columnar.DECODE_STATS[k] - before[k] for k in before}


@pytest.mark.parametrize("side", sorted(SIDES))
@pytest.mark.parametrize("dtype", TPCH_DTYPES)
def test_decoded_columns_equal_np_load(rng, dtype, side):
    n = (1 << 20) // np.dtype(dtype).itemsize
    data = columnar.serialize(ColumnBatch(
        {"a": _random_column(rng, dtype, n),
         "b": _random_column(rng, dtype, n)}))
    cols = SIDES[side]
    out, counts = _decode(data, cols)
    _assert_same(out, _reference(data, cols))
    assert counts == {"members_concurrent": len(cols) * (side == "concurrent"),
                      "members_serial": len(cols) * (side == "serial")}
    assert not any(v.flags.writeable for v in out.values())


def _tpch_like(rng, n):
    return ColumnBatch({
        "l_orderkey": rng.integers(1, 6_000_000, n),
        "l_quantity": rng.integers(1, 51, n).astype(np.float64),
        "l_partkey": rng.integers(1, 200_000, n).astype(np.int32),
        "l_returnflag": rng.integers(0, 3, n).astype(np.int8),
        "l_comment": _random_column(rng, "S44", n),
        "o_comment": _random_column(rng, "S79", n),
    })


def test_projection_order_differs_from_stored_order(rng):
    data = columnar.serialize(_tpch_like(rng, 20_000))
    cols = ["o_comment", "l_returnflag", "l_orderkey", "l_comment"]
    out, counts = _decode(data, cols)
    _assert_same(out, _reference(data, cols))
    assert counts["members_concurrent"] == 4
    one, counts = _decode(data, ["l_quantity"])
    _assert_same(one, _reference(data, ["l_quantity"]))
    assert counts["members_serial"] == 1
    whole, _ = _decode(data)
    _assert_same(whole, _reference(data, None))


def test_missing_column_raises_key_error(rng):
    data = columnar.serialize(_tpch_like(rng, 100))
    with pytest.raises(KeyError):
        columnar.deserialize(data, ["l_orderkey", "no_such_column"])


def test_stored_members_are_read_uncompressed(rng):
    buf = io.BytesIO()
    np.savez(buf, a=np.arange(1000), b=_random_column(rng, "S44", 1000))
    data = buf.getvalue()
    out = columnar.deserialize(data, ["b", "a"])
    _assert_same(out, _reference(data, ["b", "a"]))
    assert not any(v.flags.writeable for v in out.values())
    bad = _flip_inside_member(data, "a")
    with pytest.raises(zipfile.BadZipFile, match="CRC"):
        columnar.deserialize(bad, ["a"])


def test_two_dimensional_members(rng):
    grid = rng.random((300, 7))
    buf = io.BytesIO()
    np.savez_compressed(buf, c_order=grid, fortran=np.asfortranarray(grid))
    data = buf.getvalue()
    out = columnar.deserialize(data, ["c_order", "fortran"])
    _assert_same(out, _reference(data, ["c_order", "fortran"]))
    np.testing.assert_array_equal(out["fortran"], grid)
    assert out["fortran"].flags.f_contiguous


def test_object_dtype_member_refuses_pickle():
    buf = io.BytesIO()
    np.savez_compressed(buf, ints=np.arange(5),
                        objs=np.array([1, "two", None], dtype=object))
    data = buf.getvalue()
    with pytest.raises(ValueError, match="allow_pickle"):
        _reference(data, ["objs"])
    with pytest.raises(ValueError, match="allow_pickle"):
        columnar.deserialize(data, ["ints", "objs"])


def test_unknown_npy_version_raises():
    body = io.BytesIO()
    np.lib.format.write_array(body, np.arange(4))
    raw = bytearray(body.getvalue())
    raw[6] = 9                      # major version 9
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
        zf.writestr("a.npy", bytes(raw))
    with pytest.raises(ValueError):
        _reference(buf.getvalue(), ["a"])
    with pytest.raises(ValueError, match="version"):
        columnar.deserialize(buf.getvalue(), ["a"])


def test_mixed_stored_and_deflated_members(rng):
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", allowZip64=True) as zf:
        for name, arr, kind in [
                ("a", _random_column(rng, "int64", 70_000), zipfile.ZIP_DEFLATED),
                ("b", _random_column(rng, "float64", 70_000), zipfile.ZIP_DEFLATED),
                ("c", np.arange(70_000, dtype=np.int32), zipfile.ZIP_STORED)]:
            info = zipfile.ZipInfo(name + ".npy")
            info.compress_type = kind
            with zf.open(info, "w", force_zip64=True) as f:
                np.lib.format.write_array(f, arr)
    data = buf.getvalue()
    out, counts = _decode(data, ["c", "b", "a"])
    _assert_same(out, _reference(data, ["c", "b", "a"]))
    assert counts == {"members_concurrent": 3, "members_serial": 0}


def _flip_inside_member(data, name):
    """``data`` with one byte flipped in the middle of the deflate stream
    of member ``name``."""
    with zipfile.ZipFile(io.BytesIO(data)) as zf:
        info = zf.getinfo(name + ".npy")
    name_len, extra_len = struct.unpack_from("<HH", data,
                                             info.header_offset + 26)
    start = info.header_offset + 30 + name_len + extra_len
    bad = bytearray(data)
    bad[start + info.compress_size // 2] ^= 0x40
    return bytes(bad)


@pytest.mark.parametrize("side", sorted(SIDES))
def test_corrupted_member_raises(rng, side):
    data = columnar.serialize(ColumnBatch(
        {"a": _random_column(rng, "int64", 50_000),
         "b": _random_column(rng, "float64", 50_000)}))
    bad = _flip_inside_member(data, "b")
    with pytest.raises((zipfile.BadZipFile, zlib.error)):
        columnar.deserialize(bad, SIDES[side])
    _assert_same(columnar.deserialize(bad, ["a"]), _reference(data, ["a"]))


def test_pool_threads_open_no_spans(rng, monkeypatch):
    data = columnar.serialize(_tpch_like(rng, 20_000))
    ring = spans.SpanRing(capacity=64)
    monkeypatch.setattr(spans, "RING", ring)
    _, counts = _decode(data, ["l_comment", "o_comment", "l_orderkey"])
    assert counts["members_concurrent"] == 3
    records, _ = ring.snapshot()
    assert [r.name for r in records] == ["engine.codec.decode"]


def test_frames_never_reach_the_npz_decoder(rng):
    frame = columnar.serialize_frame(_tpch_like(rng, 1_000))
    out, counts = _decode(frame, ["l_comment", "l_orderkey"])
    assert list(out) == ["l_comment", "l_orderkey"]
    assert counts == {"members_concurrent": 0, "members_serial": 0}
