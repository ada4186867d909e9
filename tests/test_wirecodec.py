"""Wire codec (docs/API.md "Binary wire format"), hermetic: fuzzed
encode→decode round-trips across shapes/dtypes/NaN payloads, loud
rejection of truncated/corrupt/oversized frames (a partial batch must
never decode silently), and the typed eta/matrix/error helpers'
contracts. The served parity twin is ``tests/test_wire_serving.py``."""

import numpy as np
import pytest

from routest_tpu.serve import wirecodec as wc


# ── generic frame round-trips ────────────────────────────────────────

def test_frame_roundtrip_basic():
    cols = {
        "f32": np.arange(7, dtype=np.float32),
        "f64": np.linspace(-1, 1, 5),
        "i64": np.array([-(2 ** 62), 0, 2 ** 62], np.int64),
        "raw": b"\x00\xffhello",
    }
    frame = wc.decode_frame(wc.encode_frame(3, cols), max_bytes=1 << 20)
    assert frame.kind == 3
    assert list(frame.columns) == list(cols)  # order preserved
    for name, val in cols.items():
        got = frame.columns[name]
        if isinstance(val, bytes):
            assert bytes(got) == val
        else:
            assert got.dtype == val.dtype
            np.testing.assert_array_equal(got, val)


def test_frame_fuzz_roundtrip_bit_identical():
    rng = np.random.default_rng(0)
    dtypes = (np.float32, np.float64, np.int64)
    for trial in range(50):
        cols = {}
        for c in range(rng.integers(1, 6)):
            dt = dtypes[int(rng.integers(0, len(dtypes)))]
            n = int(rng.integers(0, 200))
            if dt is np.int64:
                arr = rng.integers(-(2 ** 60), 2 ** 60, size=n).astype(dt)
            else:
                arr = rng.normal(size=n).astype(dt)
                # salt in NaN/Inf rows: NaN payload bits must survive
                if n:
                    arr[rng.integers(0, n, size=max(1, n // 8))] = np.nan
                    arr[int(rng.integers(0, n))] = np.inf
            cols[f"c{c}"] = arr
        buf = wc.encode_frame(1, cols)
        frame = wc.decode_frame(buf, max_bytes=1 << 22)
        for name, val in cols.items():
            got = frame.columns[name]
            assert got.dtype == val.dtype
            # BIT-identical, not just value-equal: compare raw bytes so
            # NaN payloads and signed zeros count too.
            assert got.tobytes() == val.tobytes(), (trial, name)


def test_decoded_views_are_zero_copy():
    feats = np.arange(24, dtype=np.float32).reshape(2, 12)
    buf = wc.encode_eta_request(feats, np.zeros(2, np.int64))
    frame = wc.decode_eta_request(buf, max_bytes=1 << 20, max_rows=16)
    # payload() exposes the raw span of the received buffer
    assert bytes(frame.payload("features")) == feats.tobytes()
    # and the ndarray column is a view over it, not a copy
    assert frame.columns["features"].base is not None


# ── loud rejection ───────────────────────────────────────────────────

def test_truncated_frames_rejected_at_every_cut():
    buf = wc.encode_frame(1, {"a": np.arange(10, dtype=np.float32),
                              "b": np.arange(4, dtype=np.int64)})
    for cut in range(len(buf)):
        with pytest.raises(wc.WireError):
            wc.decode_frame(buf[:cut], max_bytes=1 << 20)


def test_trailing_garbage_rejected():
    buf = wc.encode_frame(1, {"a": np.arange(3, dtype=np.float32)})
    with pytest.raises(wc.WireError, match="trailing"):
        wc.decode_frame(buf + b"\x00", max_bytes=1 << 20)


def test_corrupt_header_fields_rejected():
    buf = bytearray(wc.encode_frame(1, {"a": np.zeros(4, np.float32)}))
    with pytest.raises(wc.WireError, match="magic"):
        wc.decode_frame(b"XXXX" + bytes(buf[4:]), max_bytes=1 << 20)
    bad_dtype = bytearray(buf)
    # dtype code byte sits right after magic+kind+ncols+name_len+name
    off = 4 + 1 + 2 + 2 + 1
    bad_dtype[off] = 250
    with pytest.raises(wc.WireError, match="dtype"):
        wc.decode_frame(bytes(bad_dtype), max_bytes=1 << 20)


def test_corrupt_count_never_silently_shortens():
    """Flipping any byte either round-trips to different bytes or
    raises — a corrupt frame must never decode to a silently WRONG
    batch of the advertised shape."""
    rng = np.random.default_rng(1)
    arr = rng.normal(size=64).astype(np.float32)
    buf = wc.encode_frame(1, {"x": arr})
    for _ in range(200):
        corrupt = bytearray(buf)
        i = int(rng.integers(0, len(buf)))
        corrupt[i] ^= 1 << int(rng.integers(0, 8))
        try:
            frame = wc.decode_frame(bytes(corrupt), max_bytes=1 << 20)
        except wc.WireError:
            continue
        # decoded: the defect must be visible somewhere — kind, column
        # name, or payload bytes
        assert frame.kind != 1 or list(frame.columns) != ["x"] or \
            frame.columns["x"].tobytes() != arr.tobytes()


def test_duplicate_column_rejected():
    one = wc.encode_frame(1, {"a": np.zeros(2, np.float32)})
    # splice the single column twice under one header
    head = one[:4 + 1]
    ncols = (2).to_bytes(2, "little")
    col = one[4 + 1 + 2:]
    with pytest.raises(wc.WireError, match="duplicate"):
        wc.decode_frame(head + ncols + col + col, max_bytes=1 << 20)


def test_oversized_frame_bounded_by_knob():
    buf = wc.encode_frame(1, {"a": np.zeros(1024, np.float32)})
    with pytest.raises(wc.WireError, match="exceeds"):
        wc.decode_frame(buf, max_bytes=256)
    wc.decode_frame(buf, max_bytes=len(buf))  # exact bound passes


# ── typed helpers ────────────────────────────────────────────────────

def test_eta_request_roundtrip_and_validation():
    rng = np.random.default_rng(2)
    feats = rng.normal(size=(33, 12)).astype(np.float32)
    pickup = rng.integers(0, 2 ** 48, size=33).astype(np.int64)
    frame = wc.decode_eta_request(wc.encode_eta_request(feats, pickup),
                                  max_bytes=1 << 20, max_rows=64)
    assert frame.columns["features"].shape == (33, 12)
    np.testing.assert_array_equal(frame.columns["features"], feats)
    np.testing.assert_array_equal(frame.columns["pickup_ms"], pickup)
    with pytest.raises(wc.WireError, match="rows"):
        wc.decode_eta_request(wc.encode_eta_request(feats, pickup),
                              max_bytes=1 << 20, max_rows=32)
    # mismatched pickup length is a frame defect, not a crop
    bad = wc.encode_frame(wc.K_ETA_REQUEST, {
        "features": feats.ravel(), "pickup_ms": pickup[:10]})
    with pytest.raises(wc.WireError):
        wc.decode_eta_request(bad, max_bytes=1 << 20, max_rows=64)


def test_eta_response_roundtrip_with_nan_rows():
    minutes = np.array([1.5, np.nan, 3.25], np.float64)
    comp = np.array([10_000, wc.COMPLETION_NAT, 30_000], np.int64)
    bands = {"p10": np.array([1.0, np.nan, 3.0]),
             "p90": np.array([2.0, np.nan, 4.0])}
    out = wc.decode_eta_response(
        wc.encode_eta_response(minutes, comp, bands))
    assert out["minutes"].tobytes() == minutes.tobytes()
    np.testing.assert_array_equal(out["completion_ms"], comp)
    assert sorted(out["bands"]) == ["p10", "p90"]
    for k in bands:
        assert out["bands"][k].tobytes() == bands[k].tobytes()


def test_matrix_roundtrip_matches_json_shape():
    pts = np.array([[14.6, 121.0], [14.61, 121.02], [14.59, 120.98]])
    req = wc.decode_matrix_request(
        wc.encode_matrix_request(pts, {"sources": [0],
                                       "destinations": [1, 2],
                                       "vehicle_type": "car"}),
        max_bytes=1 << 20)
    assert req["points"] == [{"lat": a, "lon": b} for a, b in pts]
    assert req["sources"] == [0] and req["destinations"] == [1, 2]
    result = {"durations_s": [[414.4, None]], "distances_m": [[1.5, 2.5]],
              "sources": [0], "destinations": [1, 2],
              "vehicle_type": "car", "road_graph": False,
              "leg_cost_model": "haversine"}
    back = wc.decode_matrix_response(wc.encode_matrix_response(result))
    assert back == result  # None rows survive (NaN on the wire)


def test_error_frames_raise_loudly_in_typed_decoders():
    ef = wc.encode_error_frame(503, "model unavailable")
    assert wc.decode_error_frame(ef) == (503, "model unavailable")
    for decode in (wc.decode_eta_response, wc.decode_matrix_response):
        with pytest.raises(wc.WireError, match="503"):
            decode(ef)
