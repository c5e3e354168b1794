"""Every cell rehearsed end to end on the CPU at a tiny size, through
``run.main`` and the same files the chip runs, steered from here: the
last line's keys, a cell added from new files alone, the faults that
have to make a run not correct, and the bf16 control."""

from __future__ import annotations

import json
import shutil

import numpy as np
import pytest

from bench_tiny import add_batch_once_cell, make_root, run_cell

#: the benchmark's cell and a batch-once cell added from new files
CELLS = ("rho-steady", "k-tiny")
SEED = 2 ** 31 + 977


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A tiny root with every configuration's deployment built, before
    any test plants a fault."""
    from harness import build, check, spec
    root = make_root(tmp_path_factory.mktemp("bench_tiny"))
    add_batch_once_cell(root, CELLS[1])
    for cell in CELLS:
        cfg = spec.load_cell(root, cell).config
        build.load_deployment(cfg, root / "bench" / ".cache",
                              check.load_reference(root, cfg["reference"]),
                              log=lambda m: None)
    return root


def _metric_names(root, cell, trace):
    from harness import spec
    c = spec.load_cell(root, cell)
    return {m["name"] for m in (c.per_layer if trace else c.end_to_end)}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end(tiny_root, cell, trace, capsys):
    rc, line = run_cell(tiny_root, cell, seed=SEED, trace=trace,
                        capsys=capsys)
    assert rc == 0 and line is not None
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 60           # 30 q/s for 2 s
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    names = set(line["metrics"])
    want = _metric_names(tiny_root, cell, trace)
    assert names <= want
    if trace:
        # off the chip there is no device trace: the readers of device
        # numbers find nothing and their metrics are left out
        assert not any("roofline" in n or "idle" in n for n in names)
        assert {n for n in want if n.startswith(("window_compiles",
                                                 "queue_wait", "stage"))
                } <= names
        assert all(line["metrics"][n]["value"] == 0 for n in names
                   if n.startswith("window_compiles"))
    else:
        assert names == want
    assert set(line["checks"]) >= {"failed", "class_miss_pct",
                                   "list_miss_pct", "order_gap_max"}


def test_same_seed_same_requests(tiny_root):
    from harness import build, check, spec, traffic
    cell = spec.load_cell(tiny_root, "rho-steady")
    dep = build.load_deployment(
        cell.config, tiny_root / "bench" / ".cache",
        check.load_reference(tiny_root, cell.config["reference"]),
        log=lambda m: None)
    assert dep.hits == {"collection": True, "reference": True,
                        "cascade": True}
    a, b = (traffic.make_schedule(cell.traffic, 2.0, SEED, dep.freq,
                                  dep.train_terms, cell.config["query_law"])
            for _ in range(2))
    np.testing.assert_array_equal(a.queries, b.queries)
    np.testing.assert_array_equal(a.due, b.due)


def test_no_tpu_no_result(tiny_root, capsys, monkeypatch, tmp_path):
    import run
    monkeypatch.setenv("TPU_LOG_DIR", str(tmp_path))
    rc = run.main(["--workload", "rho-steady", "--seed", str(SEED),
                   "--seconds", "2", "--trace", "0"], root=tiny_root)
    assert rc != 0
    assert not [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("{")]


def test_a_cell_added_from_new_files_alone(tiny_root, tmp_path, capsys):
    root = tmp_path / "more"
    shutil.copytree(tiny_root, root)
    (root / "bench" / "traffic" / "rho-slow.json").write_text(json.dumps(
        {"law": "poisson", "rate_qps": 20.0, "deadline_ms": 1000.0,
         "at_close": "drain", "trace_seconds": 1.0}))
    (root / "bench" / "metrics" / "answered.slow.py").write_text(
        "def read(run):\n    return len(run.results())\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "rho-slow", "config": "msmarco-rho",
                           "traffic": "rho-slow", "chips": 1,
                           "why": "a cell added by new files"})
    b["per_layer"].append({"name": "answered.slow", "unit": "count",
                           "better": "higher", "source": "host_clock",
                           "layer": "load generator", "moves": "p50_ms",
                           "workloads": ["rho-slow"]})
    for m in b["end_to_end"]:
        if m["name"] == "p50_ms":
            m["workloads"].append("rho-slow")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    rc, line = run_cell(root, "rho-slow", seed=SEED, trace=1, capsys=capsys)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["answered.slow"]["value"] == 40


# -- faults planted under the timed path ------------------------------------

def _answer_altered(monkeypatch):
    """Each list's top document replaced, where the engine produces it."""
    from repro.serving import engine

    def alter(ranked):
        ranked = np.array(ranked, copy=True)
        for row in ranked:
            if row[0] >= 0:
                row[0] = min(set(range(len(row) + 1)) - set(row.tolist()))
        return ranked

    serve = engine.ServingEngine.serve
    finalize = engine.SchedPrograms.finalize

    def altered_serve(self, *a, **kw):
        ranked, timings = serve(self, *a, **kw)
        return alter(ranked), timings

    def altered_finalize(self, *a, **kw):
        return alter(finalize(self, *a, **kw))

    monkeypatch.setattr(engine.ServingEngine, "serve", altered_serve)
    monkeypatch.setattr(engine.SchedPrograms, "finalize", altered_finalize)


def _class_altered(monkeypatch):
    """The cascade's class shifted by one, where it is predicted."""
    from repro.serving import pipeline
    predict = pipeline.RetrievalServer.predict_classes

    def shifted(self, qt, knob=None):
        c = np.asarray(predict(self, qt, knob=knob))
        return (c + 1) % (len(self.cfg.cutoffs) + 1)

    monkeypatch.setattr(pipeline.RetrievalServer, "predict_classes", shifted)


@pytest.mark.parametrize("fault", [_answer_altered, _class_altered],
                         ids=["answer_altered", "class_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(tiny_root, cell, fault, monkeypatch, capsys):
    fault(monkeypatch)
    rc, line = run_cell(tiny_root, cell, seed=SEED + 1, capsys=capsys)
    assert rc == 0 and line["correct"] is False
    over = [k for k, v in line["checks"].items() if v["value"] > v["limit"]]
    assert over, line["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_is_not_correct(tiny_root, cell):
    import calibrate
    from harness import check, spec
    limits = spec.load_cell(tiny_root, cell).config["limits"]
    rows = list(calibrate.readings(tiny_root, cell, [SEED + 2], [SEED + 2],
                                   seconds=2.0, log=lambda m: None))
    program, control = rows
    assert program["source"] == "program"
    assert all(program[k] <= limits[k] for k in check.NUMBERS)
    assert control["source"] == "control_bf16"
    assert any(control[k] > limits[k] for k in check.NUMBERS), control
