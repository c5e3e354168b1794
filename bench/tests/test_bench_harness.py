"""The benchmark's pieces, one by one: lookup by name, the contract of
BENCHMARK.json, trace reduction on a recorded chip trace, roofline
counts, the store, MED and the traffic generator."""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench_tiny import BENCH, REPO

from harness import data, record, roofline, spec, store, trace, traffic
from harness.check import med_rbp

TINY_TRACE = BENCH / "tests" / "data" / "tiny_v5e.xplane.pb"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench_json():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_cells_found_by_name():
    rho = spec.load_cell(REPO, "rho-steady")
    assert rho.config["name"] == "msmarco-rho"
    assert rho.config["serving"]["lifecycle"] == "continuous"
    assert {m["name"] for m in rho.end_to_end} == {
        "setup_s", "p50_ms", "in_envelope_pct"}
    assert all(m["name"].endswith(".tail") for m in rho.per_layer)
    with pytest.raises(KeyError):
        spec.load_cell(REPO, "no-such-cell")


def test_benchmark_json_keeps_the_contract(bench_json):
    b = bench_json
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]]
    used = {w["config"] for w in b["workloads"]}
    assert set(names) == used and len(set(names)) == len(names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file() and c["file"].startswith("bench/")
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        reported = [m for m in b["per_layer"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert reported, w["name"]
    layers = {}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline.tail") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    assert len(json.dumps(b)) < 64 * 1024


def test_every_metric_has_a_reader(bench_json):
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        assert callable(spec.load_reader(REPO, m["name"]))


def test_trace_reduction_on_a_recorded_chip_trace():
    t = trace.reduce_trace(str(TINY_TRACE))
    # recorded on a TPU v5 lite: an impact_scan call and a top-k, twice;
    # the second pair lies inside the "bench.window" annotation
    assert t.window_ns == (42120410.0, 45082090.0)
    scans = t.kernel_ops("impact_scan")
    assert [op.dur_ns for op in scans] == [63886.0]
    assert t.n_chips == 1 and len(t.ops) == 30
    assert t.busy_ns == 236090.0
    assert len(t.gaps) == 25
    assert sum(g1 - g0 for g0, g1 in t.gaps) == pytest.approx(
        (t.window_ns[1] - t.window_ns[0]) - t.busy_ns)
    assert t.op_seconds()["impact_scan f32[8,16384]"] == pytest.approx(
        63886e-9)


def test_shapes_parsed_from_hlo_text():
    res, args = trace.parse_shapes(
        "%impact_scan.1 = f32[8,16384]{1,0:T(8,128)} custom-call(s32[8]{0:"
        "T(128)S(1)} %copy-done, s32[16]{0} %b, s32[16]{0} %c, s32[8,1024]"
        "{1,0:T(8,128)} %d.1, f32[8,1024]{1,0:T(8,128)} %i.1), "
        "custom_call_target=\"tpu_custom_call\"")
    assert res == [("f32", (8, 16384), 524288)]
    assert [a[1] for a in args] == [(8,), (16,), (16,), (8, 1024), (8, 1024)]
    res, args = trace.parse_shapes(
        "%custom-call = (f32[8,100]{1,0}, s32[8,100]{1,0}) custom-call("
        "f32[8,16384]{1,0} %a.1), custom_call_target=\"TopK\"")
    assert [r[1] for r in res] == [(8, 100), (8, 100)]
    assert args == [("f32", (8, 16384), 524288)]


def test_impact_scan_work_by_hand():
    res = [("f32", (8, 16384), 8 * 16384 * 4)]
    args = [("s32", (8,), 32), ("s32", (16,), 64), ("s32", (16,), 64),
            ("s32", (8, 1024), 32768), ("f32", (8, 1024), 32768)]
    # one add per posting given: 8 x 1024; every operand read once, the
    # accumulator written once
    assert roofline.impact_scan_work(res, args, 16384) == (
        8192.0, 32 + 64 + 64 + 32768 + 32768 + 524288)
    # result columns past n_docs are the kernel's padding, not work
    assert roofline.impact_scan_work(res, args, 16000)[1] == (
        32 + 64 + 64 + 32768 + 32768 + 8 * 16000 * 4)
    with pytest.raises(ValueError):
        roofline.impact_scan_work(res, args[:4], 16384)


def test_roofline_share_by_hand():
    pk = roofline.peaks("TPU v5 lite")
    # 819 MB at 819 GB/s is 1 ms; spent 2 ms -> 50%, memory bound
    share, bound = roofline.roofline_share([(1e6, 819e6, 2e-3)], pk)
    assert share == pytest.approx(50.0) and bound == "memory"
    # 197 TFLOP at 197 TFLOP/s is 1 s; spent 4 s -> 25%, compute bound
    share, bound = roofline.roofline_share([(197e12, 1.0, 4.0)], pk)
    assert share == pytest.approx(25.0) and bound == "compute"
    assert roofline.roofline_share([], pk) is None
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_recorded_kernel_roofline_is_a_share():
    t = trace.reduce_trace(str(TINY_TRACE))
    run = record.Run(cell="x", config={"collection": {"n_docs": 16384}},
                     traffic={}, seconds=1.0, t_open=0.0, t_stop=1.0,
                     setup_s=1.0, outcomes=[], window_compiles=0,
                     in_envelope_pct=None, trace=t,
                     peaks=roofline.peaks("TPU v5 lite"))
    pct = run.roofline_pct("impact_scan", roofline.impact_scan_work)
    least = 589984 / 819e9
    assert pct == pytest.approx(100 * least / 63886e-9)
    assert 0 < pct < 100
    assert 0 < run.device_idle_pct() < 100


@dataclasses.dataclass
class _Leaf:
    a: np.ndarray
    pair: tuple
    note: str


def test_store_round_trip(tmp_path):
    obj = {"x": _Leaf(np.arange(5, dtype=np.int32), (1.5, 2), "n"),
           "ys": [np.ones((2, 3), np.float32), None, True]}
    store.save(obj, tmp_path / "e", "k1")
    back = store.load(tmp_path / "e", "k1")
    assert isinstance(back["x"], _Leaf) and back["x"].pair == (1.5, 2)
    np.testing.assert_array_equal(back["x"].a, obj["x"].a)
    assert back["ys"][1] is None and back["ys"][2] is True
    assert store.load(tmp_path / "e", "k2") is None
    assert store.load(tmp_path / "missing", "k1") is None


def test_med_rbp_matches_the_program():
    import jax.numpy as jnp

    from repro.core import med

    rng = np.random.default_rng(0)
    for _ in range(20):
        a = rng.choice(60, 20, replace=False).astype(np.int32)
        b = rng.choice(60, 20, replace=False).astype(np.int32)
        b[rng.integers(0, 20, 3)] = -1
        want = float(med.med_rbp(jnp.asarray(a[None]), jnp.asarray(b[None]),
                                 p=0.95)[0])
        assert med_rbp(a, b, 0.95) == pytest.approx(want, abs=1e-6)
    assert med_rbp(a, a, 0.95) == 0.0


def test_schedule_is_the_seeds_and_never_repeats():
    col = data.make_collection(2000, 4000, 40.0, 0.6, 1.07, seed=1)
    df = data.term_freq(col)
    law = {"max_len": 16, "mean_words": 6.0, "stopwords": 33}
    train = data.make_queries(df, 64, np.random.default_rng(2), **law)
    t = {"law": "poisson", "rate_qps": 50.0, "deadline_ms": 100.0}
    big = 2 ** 31 + 12345
    a = traffic.make_schedule(t, 4.0, big, df, train, law)
    b = traffic.make_schedule(t, 4.0, big, df, train, law)
    c = traffic.make_schedule(t, 4.0, big + 1, df, train, law)
    assert len(a.due) == len(c.due) == 200
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.queries, b.queries)
    assert not np.array_equal(a.due, c.due)
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] and a.due[-1] < 4
    rows = {r.tobytes() for r in a.queries}
    assert len(rows) == len(a.queries)
    assert not rows & {r.tobytes() for r in train}
    # every seed sends the same queries, the same gaps in another order
    np.testing.assert_array_equal(a.queries, c.queries)
    np.testing.assert_allclose(np.sort(np.diff(a.due)),
                               np.sort(np.diff(c.due)))
    assert a.due[0] == 0.0
    assert np.diff(a.due).mean() == pytest.approx(1 / 50.0)
    with pytest.raises(ValueError):
        traffic.make_schedule(dict(t, law="bursty"), 4.0, 1, df, train, law)


def test_nearest_rank():
    v = list(range(1, 101))
    assert record.nearest_rank(v, 0.95) == 95
    assert record.nearest_rank([3.0, float("inf")], 0.95) == float("inf")
    assert record.nearest_rank([7.0], 0.5) == 7.0


def test_traffic_and_config_files_are_found_by_name(bench_json):
    for w in bench_json["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for c in bench_json["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (BENCH / "configs" / f"{cfg['reference']}.py").is_file()
        assert set(cfg["limits"]) == {"class_miss_pct", "list_miss_pct",
                                      "order_gap_max"}
        assert Path(c["file"]).stem == c["name"]


def test_knee_rule():
    import sweep
    flat = [10, 12, 9, 11, 13, 10, 12, 11, 10, 12,
            11, 13, 10, 12, 11, 10, 12, 11, 13, 10]
    assert sweep.sustained(flat, rate=100.0, seconds=20.0)
    # 10% over capacity: the backlog grows by 10 q/s, 100 over a half
    growing = [10 * (j + 1) for j in range(20)]
    assert not sweep.sustained(growing, rate=100.0, seconds=20.0)
    # a stall of two samples near the end, drained again, is no growth
    stalled = flat[:16] + [250, 400] + flat[18:]
    assert sweep.sustained(stalled, rate=100.0, seconds=20.0)


def test_query_law():
    col = data.make_collection(3000, 6000, 40.0, 0.6, 1.07, seed=3)
    freq = data.term_freq(col)
    stop = set(np.argsort(-freq, kind="stable")[:33].tolist())
    q = data.make_queries(freq, 2000, np.random.default_rng(4),
                          max_len=16, mean_words=6.0, stopwords=33)
    n = (q >= 0).sum(axis=1)
    assert q.shape == (2000, 16) and n.min() >= 1
    assert not stop & set(q[q >= 0].tolist())
    for row, k in zip(q, n):
        assert np.all(row[k:] == -1) and np.all(np.diff(row[:k]) > 0)
    # six words on average, about a third of them on the stop list
    assert 3.0 < n.mean() < 5.0
    again = data.make_queries(freq, 2000, np.random.default_rng(4),
                              max_len=16, mean_words=6.0, stopwords=33)
    np.testing.assert_array_equal(q, again)


def _copy_sources(dst: Path) -> Path:
    from harness.build import INDEX_SOURCES, SRC
    for f in INDEX_SOURCES + ("repro/kernels/impact_scan/kernel.py",):
        (dst / f).parent.mkdir(parents=True, exist_ok=True)
        (dst / f).write_bytes((SRC / f).read_bytes())
    return dst


def test_cache_keys_follow_only_the_sources_that_make_them(tmp_path):
    from harness import build
    cfg = json.loads((BENCH / "configs" / "msmarco-rho.json").read_text())
    src = _copy_sources(tmp_path / "src")
    key = build.collection_key(cfg, src)
    ref = BENCH / "configs" / f"{cfg['reference']}.py"
    kkey = build.cascade_key(cfg, key, ref)
    # a change to a kernel or to serving finds the deployment built
    kernel = src / "repro/kernels/impact_scan/kernel.py"
    kernel.write_text(kernel.read_text() + "\n# edited\n")
    assert build.collection_key(cfg, src) == key
    assert build.cascade_key(cfg, key, ref) == kkey
    # a change to the index build builds it anew, and the cascade with it
    index = src / "repro/retrieval/index.py"
    index.write_text(index.read_text() + "\n# edited\n")
    assert build.collection_key(cfg, src) != key
    assert build.cascade_key(cfg, build.collection_key(cfg, src),
                             ref) != kkey
    other = json.loads(json.dumps(cfg))
    other["training_log"]["tau"] = 0.1
    assert build.cascade_key(other, key, ref) != kkey


def test_fitted_cascade_serves_as_the_reference_reads_it():
    import jax.numpy as jnp

    from harness import cascade
    from harness.check import load_reference
    from repro.core import cascade as program_cascade
    from repro.core import forest as program_forest

    rng = np.random.default_rng(5)
    x = rng.normal(size=(400, 70)).astype(np.float32)
    classes = np.clip((x[:, 3] * 2 + x[:, 7] + 4).astype(np.int64), 0, 9)
    depth = 6
    tables = cascade.fit_cascade(x, classes, 9, n_trees=10, max_depth=depth,
                                 seed=0)
    cap = program_forest.node_capacity(depth)
    for t in tables:
        assert t["feature"].shape[1] <= cap
        leaves = t["feature"] < 0
        np.testing.assert_allclose(t["leaf"].sum(-1)[leaves], 1.0,
                                   rtol=1e-6)
    params = [program_forest.pad_forest_params(t, cap) for t in tables]
    served = np.asarray(program_cascade.classes_from_proba(
        program_cascade.proba0_from_params("forest", params, jnp.asarray(x),
                                           depth), 0.75))
    ref_mod = load_reference(REPO, "two_stage")
    ref = ref_mod.Reference.__new__(ref_mod.Reference)
    ref.threshold = 0.75
    ref.features = lambda q: x
    np.testing.assert_array_equal(served, ref.classes(x, tables))
    # the forest learnt the rule it was shown
    assert np.corrcoef(served, classes)[0, 1] > 0.7


def test_split_thresholds_lie_between_training_values():
    from harness import cascade
    rng = np.random.default_rng(5)
    # few distinct values, so the quantile edges are training values
    x = rng.choice(np.float32([0.1, 0.7, 2.3619673, 5.0]), (400, 3))
    y = (x[:, 0] > 1.0).astype(np.int64)
    t = cascade.fit_forest(x, y, n_trees=4, max_depth=3, seed=0)
    inner = t["feature"] >= 0
    assert inner.any()
    vals = set(np.unique(x).tolist())
    for f, thr in zip(t["feature"][inner], t["thresh"][inner]):
        assert float(thr) not in vals
        xs = np.unique(x[:, f])
        assert xs.min() < thr < xs.max()
    # the fitted split still separates the training labels
    root = t["feature"][:, 0] == 0
    assert np.all(t["thresh"][root, 0] > 0.7) and \
        np.all(t["thresh"][root, 0] < 2.3619673)
