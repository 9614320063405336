"""The JSON codec of the served answer (`repro.server.protocol`).

`jsonable` converts a numeric ndarray in one vectorised step and a reply's
body is walked once, by `http_response`. These tests hold both to the
element-by-element codec they replaced, kept here as the oracle: the bytes
on the wire must not change, non-finite floats included.
"""
from __future__ import annotations

import json
import math

import numpy as np
import pytest

from repro.api.types import SearchResult
from repro.core.topk import TopK
from repro.server import protocol


# ------------------------------------------------------------------ oracle
def _old_jsonable(obj):
    if isinstance(obj, dict):
        return {str(k): _old_jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_old_jsonable(v) for v in obj]
    if isinstance(obj, (set, frozenset)):
        return sorted(_old_jsonable(v) for v in obj)
    if isinstance(obj, np.ndarray):
        return [_old_jsonable(v) for v in obj.tolist()]
    if isinstance(obj, (bool, np.bool_)):
        return bool(obj)
    if isinstance(obj, (int, np.integer)):
        return int(obj)
    if isinstance(obj, (float, np.floating)):
        f = float(obj)
        return f if math.isfinite(f) else None
    return obj


def _old_encode_result(result):
    stats = dict(result.stats)
    shed = bool(stats.get("shed", False))
    body = {
        "rid": result.rid,
        "mode": stats.get("mode"),
        "tier": result.tier,
        "shed": shed,
        "partial": bool(stats.get("partial", False)),
        "stats": {
            "latency_ms": stats.get("latency_ms"),
            "batched": stats.get("batched"),
            "deadline_ms": stats.get("deadline_ms"),
            "health": stats.get("health", {}),
        },
    }
    if shed:
        body["scores"] = []
        body["indices"] = []
        body["certified"] = False
    else:
        body["scores"] = np.asarray(result.scores)
        body["indices"] = np.asarray(result.indices)
        body["certified"] = result.certified
    return _old_jsonable(body)


def _old_http_response(status, payload):
    body = json.dumps(_old_jsonable(payload), allow_nan=False).encode()
    head = (f"HTTP/1.1 {status} OK\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: keep-alive\r\n\r\n")
    return head.encode("latin-1") + body


# ------------------------------------------------------------------- cases
def _scores(shape, bad=()):
    rng = np.random.default_rng(7)
    s = np.sort(rng.standard_normal(shape).astype(np.float32) * 37.5, axis=-1)
    flat = s.reshape(-1)
    for i, v in bad:
        flat[i] = v
    return s


def _indices(shape, dtype):
    rng = np.random.default_rng(8)
    return rng.integers(0, 2_000_000, size=shape).astype(dtype)


def _stats(**extra):
    stats = {
        "mode": "fqsd", "latency_ms": np.float64(12.375), "batched":
        np.int64(256), "deadline_ms": None, "partial": np.bool_(False),
        "health": {"retries": np.int32(0), "failed_shards": [],
                   "degraded": np.bool_(False), "slow_shards": (),
                   "shed": False},
    }
    stats.update(extra)
    return stats


def _result(scores, indices, certified=True, stats=None, rid=41):
    return SearchResult(topk=TopK(scores=scores, indices=indices), plan=None,
                        certified=certified,
                        stats=_stats() if stats is None else stats, rid=rid)


_K = 1024
_RESULTS = {
    "f32-finite-i32": lambda: _result(
        _scores((1, _K)), _indices((1, _K), np.int32)),
    "f32-inf-nan-i32": lambda: _result(
        _scores((1, _K), bad=[(0, np.nan), (511, np.inf), (1022, -np.inf),
                              (1023, np.inf)]),
        _indices((1, _K), np.int32)),
    "f32-all-inf": lambda: _result(
        np.full((1, _K), np.inf, np.float32),
        np.full((1, _K), -1, np.int32)),
    "i64-indices": lambda: _result(
        _scores((1, _K)), _indices((1, _K), np.int64)),
    "bool-array-certified": lambda: _result(
        _scores((1, _K)), _indices((1, _K), np.int32),
        certified=np.array([True])),
    "python-bool-certified": lambda: _result(
        _scores((1, _K)), _indices((1, _K), np.int32), certified=False),
    "numpy-scalar-stats": lambda: _result(
        _scores((1, 8)), _indices((1, 8), np.int64), rid=np.int64(9),
        stats=_stats(latency_ms=np.float32(np.inf), batched=np.uint8(3),
                     deadline_ms=np.float16(250.0))),
    "shed-envelope": lambda: _result(
        np.full((1, _K), np.inf, np.float32),
        np.full((1, _K), -1, np.int32),
        stats=_stats(mode="shed", shed=True, latency_ms=np.nan)),
    "2d-3x5": lambda: _result(
        _scores((3, 5), bad=[(4, np.nan), (7, -np.inf)]),
        _indices((3, 5), np.int32), certified=np.array([True, False, True])),
    "0-length": lambda: _result(
        np.zeros((0,), np.float32), np.zeros((0,), np.int64)),
    "1-d-row": lambda: _result(
        _scores((_K,), bad=[(3, np.inf)]), _indices((_K,), np.int32)),
}


@pytest.mark.parametrize("case", sorted(_RESULTS))
def test_search_reply_bytes_match_the_element_wise_codec(case):
    r = _RESULTS[case]()
    got = protocol.http_response(200, protocol.encode_result(r))
    assert got == _old_http_response(200, _old_encode_result(r))
    # strict JSON: no NaN or Infinity constant on the wire
    json.loads(got.split(b"\r\n\r\n", 1)[1],
               parse_constant=lambda c: pytest.fail(f"emitted {c}"))


def test_encode_result_leaves_the_top_k_as_arrays():
    r = _RESULTS["f32-finite-i32"]()
    body = protocol.encode_result(r)
    assert isinstance(body["scores"], np.ndarray)
    assert isinstance(body["indices"], np.ndarray)
    assert body["scores"].dtype == np.float32
    assert body["indices"].dtype == np.int32
    shed = protocol.encode_result(_RESULTS["shed-envelope"]())
    assert shed["scores"] == [] and shed["indices"] == []
    assert shed["certified"] is False


# --------------------------------------------------------- jsonable itself
_PAYLOADS = {
    "nested-non-finite": lambda: {
        "a": [1.5, float("inf"), {"b": (np.float32(np.nan), -0.0)}],
        3: {"c": [[np.float64(-np.inf)], np.array([np.nan, 2.0])]},
        "d": {np.float32(2.5), np.int8(1), 0.5}},
    "object-array": lambda: {
        "o": np.array([1.5, np.inf, None, "x", np.float32(np.nan),
                       np.int16(4), True], dtype=object)},
    "object-array-2d": lambda: np.array([[np.nan, 1], ["y", None]],
                                        dtype=object),
    "float16-float64": lambda: [
        np.array([0.1, np.inf, -0.0, 65504.0], np.float16),
        np.array([0.1, np.nan, 1e308, -5e-324], np.float64)],
    "longdouble": lambda: np.array(
        [np.longdouble("0.1"), np.inf, np.longdouble("1e4000"), 3.0],
        np.longdouble),
    "ints-and-bools": lambda: {
        "u64": np.array([0, 2**64 - 1], np.uint64),
        "i8": np.array([-128, 127], np.int8),
        "b": np.array([[True, False], [False, True]])},
    "3-d-strided": lambda: np.where(
        np.arange(60).reshape(3, 4, 5) % 7 == 0, np.float32(np.inf),
        np.arange(60, dtype=np.float32).reshape(3, 4, 5) / 7)[:, ::2, ::-2],
    "empty-shapes": lambda: [np.zeros((0, 5), np.float32),
                             np.zeros((3, 0), np.int32),
                             np.zeros((0,), bool)],
    "scalars": lambda: [np.bool_(True), np.int64(-3), np.uint32(7),
                        np.float32(0.1), np.float64(np.inf), None, "s"],
}


@pytest.mark.parametrize("case", sorted(_PAYLOADS))
def test_jsonable_matches_the_element_wise_codec(case):
    payload = _PAYLOADS[case]()
    got = protocol.jsonable(payload)
    want = _old_jsonable(payload)
    # strict JSON (allow_nan=False raises on inf/nan), the same bytes
    assert json.dumps(got, allow_nan=False) == json.dumps(want,
                                                          allow_nan=False)
    assert got == want
    assert _types(got) == _types(want)


def _types(obj):
    if isinstance(obj, dict):
        return {k: _types(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_types(v) for v in obj]
    return type(obj).__name__


def test_jsonable_nulls_every_non_finite_float_at_any_depth():
    out = protocol.jsonable({
        "x": {"y": [np.float32(np.inf), {"z": np.array([[1.0, np.nan]])}]},
        "w": (float("-inf"),)})
    assert out == {"x": {"y": [None, {"z": [[1.0, None]]}]}, "w": [None]}
    json.dumps(out, allow_nan=False)


def test_jsonable_walks_object_arrays_element_by_element():
    arr = np.array([np.float32(2.5), np.inf, np.int64(3), np.bool_(True),
                    {"k": np.nan}], dtype=object)
    out = protocol.jsonable(arr)
    assert out == [2.5, None, 3, True, {"k": None}]
    assert [type(v) for v in out[:4]] == [float, type(None), int, bool]
    json.dumps(out, allow_nan=False)


def test_jsonable_of_a_zero_d_array_still_refuses():
    # a 0-d array was never a JSON value of the codec; it stays an error
    with pytest.raises(TypeError):
        protocol.jsonable(np.array(1.5, np.float32))
