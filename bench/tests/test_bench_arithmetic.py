"""The benchmark's own arithmetic, on the CPU: trace reduction, roofline
work, the client's latency and plan arithmetic, and the float64 reference."""
from __future__ import annotations

import json
import math

import numpy as np
import pytest

from bench import client, readers, reference, trace, work
from bench.cell import BENCH_DIR, ROOT, Cell, load_cell, peaks

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


# ------------------------------------------------------------------ trace
def synthetic_trace() -> trace.Trace:
    """Two ops meet at 1.0, the first starting before the window [0.5, 5];
    the device idles [2, 3] during a dispatch span, [3.5, 4] during an
    encode span and [4.5, 5] with no span."""
    ops = [("fusion.1", 1.0, 2.0), ("sort.2", 0.2, 1.0), ("fusion.1", 3.0, 3.5),
           ("dot.3", 4.0, 4.5)]
    spans = [("clock", 0.25, 0.25),
             ("dispatch", 1.8, 3.1), ("encode", 3.2, 4.2),
             ("decode", 2.2, 2.4)]
    return trace.Trace(device_ops={"/device:TPU:0": ops}, host_spans=spans)


def test_busy_union_clips_and_merges_overlaps():
    tr = synthetic_trace()
    busy = trace.merged(((s, e) for _, s, e in tr.device_ops["/device:TPU:0"]),
                        0.5, 5.0)
    assert busy == [(0.5, 2.0), (3.0, 3.5), (4.0, 4.5)]
    assert trace.total(busy) == pytest.approx(2.5)
    assert trace.complement(busy, 0.5, 5.0) == [(2.0, 3.0), (3.5, 4.0),
                                                 (4.5, 5.0)]


def test_reduce_gives_busy_idle_share_ops_and_gap_labels():
    tr = synthetic_trace()
    assert trace.anchor(tr) == 0.25
    red = trace.reduce(tr, 0.5, 5.0)
    assert red["busy_s"] == pytest.approx(2.5)
    assert red["window_s"] == pytest.approx(4.5)
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(1.5)
    assert ops["sort.2"] == pytest.approx(0.5)  # clipped at the window start
    assert ops["dot.3"] == pytest.approx(0.5)
    assert sum(ops.values()) == pytest.approx(red["busy_s"])
    gaps = dict(red["idle_gaps"])
    # [2, 3]: dispatch covers [2, 3] entirely (decode inside it loses);
    # [3.5, 4]: encode; [4.5, 5]: nothing
    assert gaps["dispatch"] == pytest.approx(1.0)
    assert gaps["encode"] == pytest.approx(0.5)
    assert gaps[trace.NO_SPAN] == pytest.approx(0.5)
    assert "decode" not in gaps
    assert sum(gaps.values()) == pytest.approx(red["window_s"] - red["busy_s"])
    rec = {"trace": red, "answered": 9}
    assert readers.device_idle_pct(rec) == pytest.approx(100 * 2.0 / 4.5)
    assert readers.device_ms_per_query(rec) == pytest.approx(2500 / 9)


def test_reduce_averages_devices_and_needs_device_ops():
    tr = synthetic_trace()
    tr.device_ops["/device:TPU:1"] = [("fusion.1", 0.5, 5.0)]
    red = trace.reduce(tr, 0.5, 5.0)
    assert red["busy_s"] == pytest.approx((2.5 + 4.5) / 2)
    assert trace.reduce(trace.Trace({}, tr.host_spans), 0.5, 5.0) is None
    assert trace.anchor(trace.Trace({}, [("dispatch", 1, 2)])) is None


def test_self_time_counts_nested_ops_once_and_names_are_short():
    loop = ("%while.2 = (s32[], f32[256,1024]) while(%tuple.34), body=%b", 0.0, 10.0)
    body = [("%fusion.35 = f32[262144]{0} fusion(%x), kind=kCustom", 1.0, 3.0),
            ("%sort.23 = (f32[256,8840]) sort(%y), dimensions={1}", 4.0, 8.0)]
    inner = ("%copy.1 = f32[8]{0} copy(%z)", 5.0, 6.0)
    st = trace.self_times([loop, *body, inner], 0.0, 10.0)
    assert st[loop[0]] == pytest.approx(4.0)
    assert st[body[1][0]] == pytest.approx(3.0)
    assert st[inner[0]] == pytest.approx(1.0)
    assert sum(st.values()) == pytest.approx(10.0)
    clipped = trace.self_times([loop, *body], 2.0, 5.0)
    assert clipped[loop[0]] == pytest.approx(3.0 - 1.0 - 1.0)
    assert trace.short_name(body[0][0]).startswith("fusion.35 = f32[262144]")
    assert len(trace.short_name("x" * 500)) == 100


def test_interval_set_operations():
    a = [(0.0, 2.0), (3.0, 5.0)]
    b = [(1.0, 4.0)]
    assert trace.intersect(a, b) == [(1.0, 2.0), (3.0, 4.0)]
    assert trace.subtract(a, b) == [(0.0, 1.0), (4.0, 5.0)]
    assert trace.subtract(a, []) == a


# ------------------------------------------------------------------- work
@pytest.mark.parametrize("config,m,nbytes,flops,bound", [
    ("gist1m-l2", 256, 1_000_000 * 960 * 4 + 256 * 960 * 4,
     2 * 256 * 1_000_000 * 960, "bytes"),
    ("gist1m-l2", 1, 1_000_000 * 960 * 4 + 960 * 4,
     2 * 1_000_000 * 960, "bytes"),
    ("msmarco-share4-ip", 1, 2_210_456 * 769 * 4 + 769 * 4,
     2 * 2_210_456 * 769, "bytes"),
    ("msmarco-share4-ip", 256, 2_210_456 * 769 * 4 + 256 * 769 * 4,
     2 * 256 * 2_210_456 * 769, "bytes"),
])
def test_scan_work_at_the_configurations_shapes(config, m, nbytes, flops,
                                                 bound):
    cfg = json.loads((BENCH_DIR / "configs" / f"{config}.json").read_text())
    assert work.scan_work(m, cfg["rows"], cfg["dim"]) == (nbytes, flops)
    t, which = work.least_time_s(m, cfg["rows"], cfg["dim"], V5E)
    assert which == bound
    assert t == pytest.approx(max(nbytes / 819e9, flops / 197e12))


def test_flops_bind_only_for_deep_batches():
    # 2 m n d / 197e12 > 4 n d / 819e9 once m > 2 * 197e12 / 819e9 ~ 481
    assert work.least_time_s(512, 1_000_000, 960, V5E)[1] == "flops"
    assert work.least_time_s(480, 1_000_000, 960, V5E)[1] == "bytes"


def test_scan_roofline_reader():
    busy = 0.5
    rec = {"trace": {"busy_s": busy, "window_s": 1.0}, "peaks": V5E,
           "dispatch_rows": [256, 256, 3], "rows": 1_000_000, "dim": 960}
    least = sum(work.least_time_s(m, 1_000_000, 960, V5E)[0]
                for m in (256, 256, 3))
    assert readers.dispatch_roofline(rec) == pytest.approx(100 * least / busy)
    assert readers.dispatch_roofline(dict(rec, trace=None)) is None
    assert readers.dispatch_roofline(dict(rec, dispatch_rows=[])) is None


def test_peaks_table_has_the_v5e_and_refuses_others():
    p = peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError):
        peaks("TPU v9 imaginary")


# ----------------------------------------------------------------- client
def test_encoded_requests_carry_the_float32_vectors_exactly():
    rng = np.random.default_rng(3)
    pool = rng.standard_normal((4, 7), dtype=np.float32) * 1e3
    raws = client.encode_requests(pool, "/v1/collections/c/search", "h", 5)
    for row, raw in zip(pool, raws):
        head, body = raw.split(b"\r\n\r\n", 1)
        assert f"Content-Length: {len(body)}".encode() in head
        payload = json.loads(body)
        assert payload["k"] == 5
        back = np.asarray(payload["queries"], np.float32)
        assert np.array_equal(back, row)


def test_answered_needs_200_and_a_top_k():
    assert client.answered(200, b'{"shed": false, "scores": []}')
    assert not client.answered(200, b'{"shed": true}')
    assert not client.answered(503, b'{"shed": false}')


def write_records(path, rows, kept=(), k=2):
    rec = client.Records(np.ones(8, bool))
    for p, due, sent, done, status, ok in rows:
        i = rec.start(p, due, sent)
        if done is not None:
            rec.rows[i][3:] = [done, status, ok]
            rec.kept[i] = json.dumps({"indices": [p, p + 1],
                                      "scores": [0.0, 1.0]}).encode()
    client.write_records(str(path), rec, k,
                         {"extra_connections": 0, "jax_imported": False})


def test_latency_is_timed_from_the_due_time(tmp_path):
    from bench import harness

    # window [10, 12]; request 2 was sent 0.5 s late; request 3 never came
    rows = [(0, 10.0, 10.0, 10.1, 200, True),
            (1, 10.5, 10.5, 10.7, 200, True),
            (2, 11.0, 11.5, 11.6, 200, True),
            (3, 11.5, 11.5, None, -1, False)]
    write_records(tmp_path / "r.npz", rows)
    res = harness.client_results(tmp_path / "r.npz", 2.0, 10.0, 12.0, "open")
    assert res["attempted"] == 4 and res["failed"] == 1
    lat = res["latency_s"]
    assert lat[:3] == pytest.approx([0.1, 0.2, 0.6])
    # the unanswered request counts as answered when the run gave up on it
    assert lat[3] == pytest.approx(12.0 + harness.GRACE_S - 11.5)
    assert np.percentile(lat, 50) == pytest.approx(0.4)
    assert res["lateness_max_ms"] == pytest.approx(500.0)
    pool_idx, ids, scores = res["check"]
    assert list(pool_idx) == [0, 1, 2, 3]
    assert ids[3].tolist() == [-1, -1]  # never answered: a bad answer


def test_closed_loop_counts_only_answers_inside_the_window(tmp_path):
    from bench import harness

    rows = [(0, 10.0, 10.0, 10.5, 200, True),
            (1, 11.0, 11.0, 12.5, 200, True),   # answered after the close
            (2, 11.5, 11.5, 11.9, 503, False),
            (3, 12.2, 12.2, 12.3, 200, True)]   # sent after the close
    write_records(tmp_path / "r.npz", rows)
    res = harness.client_results(tmp_path / "r.npz", 2.0, 10.0, 12.0,
                                 "closed")
    assert res["attempted"] == 3 and res["failed"] == 1
    assert res["answered_in_window"] == 1
    assert len(res["check"][0]) == 3


def tiny_cell(loop: str) -> Cell:
    from bench.tests.rehearse import CONFIG, TRAFFIC

    return Cell(name="t", chips=1, config=CONFIG, traffic=TRAFFIC[loop],
                end_to_end=(), per_layer=())


def test_open_loop_plan_offers_the_same_arrivals_for_every_seed():
    from bench import harness

    cell = tiny_cell("open")
    pool = np.zeros((64, 3), np.float32)
    a = harness.make_plan(cell, 5, 4.0, pool)
    b = harness.make_plan(cell, 2**41 + 9, 4.0, pool)
    n = int(cell.traffic["rate_qps"] * 4.0)
    assert len(a["offsets"]) == n and np.array_equal(a["offsets"], b["offsets"])
    assert 0 < a["offsets"][0] and a["offsets"][-1] < 4.0
    assert np.all(np.diff(a["offsets"]) > 0)
    # the seed draws which query arrives when
    assert not np.array_equal(a["order"], b["order"])
    again = harness.make_plan(cell, 5, 4.0, pool)
    assert all(np.array_equal(a[key], again[key]) for key in a)
    assert 0 < a["keep"].sum() <= cell.traffic["sample"]


def test_closed_loop_plan_samples_entries_sent_first():
    from bench import harness

    cell = tiny_cell("closed")
    pool = np.zeros((64, 3), np.float32)
    plan = harness.make_plan(cell, 11, 1.0, pool)
    assert sorted(plan["order"]) == list(range(64))
    first = set(plan["order"][: cell.traffic["sample_span"]].tolist())
    assert set(np.flatnonzero(plan["keep"]).tolist()) <= first
    assert plan["keep"].sum() == cell.traffic["sample"]


# -------------------------------------------------------------- reference
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_f64_topk_is_the_exact_ranking(metric):
    rng = np.random.default_rng(7)
    x = rng.standard_normal((500, 12)).astype(np.float32)
    qs = rng.standard_normal((6, 12)).astype(np.float32)
    ids, scores = reference.f64_topk(x, qs, 20, metric, block=64)
    full = reference.f64_scores(x, qs, metric)
    for j in range(len(qs)):
        want = np.lexsort((np.arange(500), full[j]))[:20]
        assert ids[j].tolist() == want.tolist()
        assert scores[j] == pytest.approx(full[j][want])
    if metric == "ip":
        assert full[0, 3] == pytest.approx(-float(qs[0].astype(np.float64)
                                                  @ x[3].astype(np.float64)))


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_compare_passes_exact_answers_and_catches_wrong_ones(metric):
    rng = np.random.default_rng(8)
    x = rng.standard_normal((400, 16)).astype(np.float32)
    pool = rng.standard_normal((5, 16)).astype(np.float32)
    k = 10
    ids, scores = reference.f64_topk(x, pool, k, metric)
    entries = np.arange(5)
    ref = reference.Reference(x, pool, metric, k, entries)
    assert np.array_equal(ref.ids, ids)
    good = ref.compare(entries, ids, scores)
    assert good.checked == 5 and good.bad_answers == 0
    assert good.score_err < 1e-6 and good.rank_gap < 1e-6
    wrong = ids.copy()
    wrong[2, 0] = ids[2, -1] + 1 if ids[2, -1] + 1 < 400 else 0
    while wrong[2, 0] in ids[2]:
        wrong[2, 0] = (wrong[2, 0] + 1) % 400
    bad = ref.compare(entries, wrong, scores)
    assert bad.score_err > 1e3 and bad.rank_gap > 1e3
    dup = ids.copy()
    dup[1, 1] = dup[1, 0]
    missing = ids.copy()
    missing[4] = -1
    nan = scores.copy()
    nan[0, 3] = np.nan
    assert ref.compare(entries, dup, scores).bad_answers == 1
    assert ref.compare(entries, missing, scores).bad_answers == 1
    assert ref.compare(entries, ids, nan).bad_answers == 1


def test_term_scale():
    q = np.array([3.0, 4.0])
    rows = np.array([[0.0, 1.0], [6.0, 8.0]])
    assert reference.term_scale(q, rows, "l2") == pytest.approx(225.0)
    assert reference.term_scale(q, rows, "ip") == pytest.approx(50.0)


# ------------------------------------------------------------- the cells
def test_every_cell_finds_its_files_and_readers():
    from bench.cell import load_reader

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert (BENCH_DIR / "limits" / f"{w['name']}.json").is_file()
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        names = {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(load_reader(m["name"]))
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert all(key in cfg for key in c["reduced"])
        assert math.ceil(cfg.get("published", {}).get("rows", cfg["rows"])
                         / cfg.get("published", {}).get("chips", 1)) == cfg["rows"]


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_certified_candidate_pass_equals_the_brute_force(metric):
    rng = np.random.default_rng(9)
    centers = rng.standard_normal((8, 24)).astype(np.float32) * 2
    x = (centers[rng.integers(0, 8, 6000)]
         + 0.5 * rng.standard_normal((6000, 24))).astype(np.float32)
    qs = (x[:7] + 0.3 * rng.standard_normal((7, 24))).astype(np.float32)
    for k in (1, 50, 400):
        want = reference.f64_topk(x, qs, k, metric)
        got = reference.exact_topk(x, qs, k, metric, block=1000)
        assert np.array_equal(got[0], want[0])
        assert got[1] == pytest.approx(want[1], rel=1e-12, abs=1e-9)


def test_uncertified_queries_fall_back_to_the_brute_force(monkeypatch):
    # every row at the same distance: the candidates cannot be certified
    x = np.ones((300, 4), np.float32)
    qs = np.zeros((2, 4), np.float32)
    calls = []
    real = reference.f64_topk

    def spy(*args, **kwargs):
        calls.append(len(args[1]))
        return real(*args, **kwargs)

    monkeypatch.setattr(reference, "f64_topk", spy)
    ids, scores = reference.exact_topk(x, qs, 10, "l2", block=64)
    assert calls == [2]
    assert all(len(set(row)) == 10 for row in ids.tolist())
    assert np.all(scores == 4.0)
