"""A continuous cell on the k knob, added to a tiny root from new files
alone as ``msmarco-k``/``k-steady`` are, rehearsed on the CPU: correct on
two seeds, with the same lists for the same requests whatever the order
of the gaps, and the k readers reading where the program counts."""

from __future__ import annotations

import json
import shutil

import pytest

from bench_tiny import BENCH, make_root, run_cell, shrink_config

CELL = "k-cont-tiny"
SEEDS = (2 ** 31 + 1601, 2 ** 31 + 2502)


@pytest.fixture(scope="module")
def k_root(tmp_path_factory):
    """A tiny root with a continuous k cell added from new files and
    entries alone, its deployment built."""
    from harness import build, check, spec
    root = make_root(tmp_path_factory.mktemp("bench_k"))
    bench = root / "bench"
    cfg = shrink_config(json.loads(
        (BENCH / "configs" / "msmarco-k.json").read_text()))
    cfg["name"] = "tiny-k-cont"
    assert cfg["serving"]["lifecycle"] == "continuous"
    assert max(cfg["serving"]["cutoffs"]) > 128   # past the Pallas top-k
    (bench / "configs" / "tiny-k-cont.json").write_text(json.dumps(cfg))
    traffic = json.loads((BENCH / "traffic" / "k-steady.json").read_text())
    traffic.update(rate_qps=30.0, trace_seconds=1.0)
    (bench / "traffic" / f"{CELL}.json").write_text(json.dumps(traffic))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-k-cont", "source": "tiny",
                         "file": "bench/configs/tiny-k-cont.json",
                         "reduced": [], "why": "k knob, continuous"})
    b["workloads"].append({"name": CELL, "config": "tiny-k-cont",
                           "traffic": CELL, "chips": 1,
                           "why": "k knob, continuous, drained"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "k-steady" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    build.load_deployment(cfg, bench / ".cache",
                          check.load_reference(root, cfg["reference"]),
                          log=lambda m: None)
    assert spec.load_cell(root, CELL).config["name"] == "tiny-k-cont"
    return root


def _served(k_root, seed, monkeypatch, capsys, trace=0):
    """One run of the cell; returns its last line and the served list of
    each answered request by request id."""
    from harness import serve
    outcomes = []
    run_window = serve.run_window

    def kept(*a, **kw):
        outcomes.extend(run_window(*a, **kw))
        return outcomes

    monkeypatch.setattr(serve, "run_window", kept)
    rc, line = run_cell(k_root, CELL, seed=seed, trace=trace,
                        capsys=capsys)
    monkeypatch.setattr(serve, "run_window", run_window)
    assert rc == 0 and line is not None
    lists = {o.result["trace_id"]: o.result["ranked"].tolist()
             for o in outcomes if o.result is not None}
    return line, lists


def test_k_cell_same_lists_on_every_seed(k_root, monkeypatch, capsys):
    lines, lists = zip(*(_served(k_root, s, monkeypatch, capsys)
                         for s in SEEDS))
    for line in lines:
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] == 60            # 30 q/s for 2 s
    env = [line["metrics"]["in_envelope_pct"]["value"] for line in lines]
    assert env[0] == env[1]
    assert len(lists[0]) == 60 and lists[0] == lists[1]


def test_k_cell_traced_reads_the_k_counters(k_root, monkeypatch, capsys):
    line, _ = _served(k_root, SEEDS[0], monkeypatch, capsys, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    cutoffs = shrink_config(json.loads(
        (BENCH / "configs" / "msmarco-k.json").read_text()))[
            "serving"]["cutoffs"]
    assert 100.0 * min(cutoffs) / max(cutoffs) <= \
        m["pool_k_pct.tail"]["value"] <= 100.0
    # every k slot runs its whole stream: at least one chunk tick each
    assert m["chunks_per_req.tail"]["value"] >= 1.0
    assert m["window_compiles.tail"]["value"] == 0


def test_k_readers_say_nothing_without_the_counters(k_root):
    """Where the program keeps no such counters (a program older than
    them), the readers return None and the line leaves the metrics out."""
    from harness import record, spec
    cell = spec.load_cell(k_root, CELL)
    run = record.Run(cell=CELL, config=cell.config, traffic=cell.traffic,
                     seconds=2.0, t_open=0.0, t_stop=2.0, setup_s=1.0,
                     outcomes=[], window_compiles=0, in_envelope_pct=None,
                     counters={"sched.ticks": 3})
    for name in ("pool_k_pct.tail", "chunks_per_req.tail"):
        assert spec.load_reader(k_root, name)(run) is None
    run.counters = {"sched.finalized": 4, "sched.k_width": 4 * 200,
                    "sched.chunk_ticks": 12}
    top = max(cell.config["serving"]["cutoffs"])
    assert spec.load_reader(k_root, "pool_k_pct.tail")(run) == \
        100.0 * 200 / top
    assert spec.load_reader(k_root, "chunks_per_req.tail")(run) == 3.0
    rho = spec.load_cell(k_root, "rho-steady")
    run.config = rho.config
    assert spec.load_reader(k_root, "pool_k_pct.tail")(run) is None
    assert spec.load_reader(k_root, "chunks_per_req.tail")(run) == 3.0
