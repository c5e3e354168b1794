"""A tiny copy of the benchmark for CPU rehearsals: the same files, the
same code path, at sizes a test run holds."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: cutoff grids at the tiny stream cap (rho) and pool (k)
TINY_CUTOFFS = {"rho": [8, 8, 8, 10, 20, 51, 102, 204, 512],
                "k": [20, 50, 100, 200, 400, 400, 400, 400, 400]}


def shrink_config(cfg: dict) -> dict:
    cfg["collection"].update(n_docs=3000, vocab=6000)
    cfg["training_log"].update(n_queries=128)
    s = cfg["serving"]
    s.update(stream_cap=512, slots=16, max_batch=16,
             cutoffs=TINY_CUTOFFS[s["knob"]])
    return cfg


def make_root(dst: Path) -> Path:
    """``dst`` holding BENCHMARK.json and bench/{configs,traffic,metrics}
    with every configuration and traffic mix cut to a tiny size."""
    (dst / "bench").mkdir(parents=True, exist_ok=True)
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / d, dst / "bench" / d, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for p in (dst / "bench" / "configs").glob("*.json"):
        p.write_text(json.dumps(shrink_config(json.loads(p.read_text()))))
    for p in (dst / "bench" / "traffic").glob("*.json"):
        t = json.loads(p.read_text())
        t.update(rate_qps=30.0, trace_seconds=1.0)
        p.write_text(json.dumps(t))
    return dst


def add_batch_once_cell(root: Path, name: str = "k-tiny") -> str:
    """A batch-once cell on the k knob, added to ``root`` from new files
    and entries alone, as a later PR adds a cell."""
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "msmarco-rho.json").read_text())
    cfg["name"] = "tiny-k"
    cfg["serving"].update(knob="k", lifecycle="batch-once",
                          cutoffs=TINY_CUTOFFS["k"])
    (bench / "configs" / "tiny-k.json").write_text(json.dumps(cfg))
    (bench / "traffic" / f"{name}.json").write_text(json.dumps(
        {"law": "poisson", "rate_qps": 30.0, "deadline_ms": 10000.0,
         "at_close": "cancel", "trace_seconds": 1.0}))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-k", "source": "tiny",
                         "file": "bench/configs/tiny-k.json", "reduced": [],
                         "why": "k knob, batch-once"})
    b["workloads"].append({"name": name, "config": "tiny-k",
                           "traffic": name, "chips": 1,
                           "why": "k knob, batch-once, queue cancelled"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return name


def run_cell(root: Path, workload: str, *, seed: int = 4242424242,
             seconds: float = 2.0, trace: int = 0, capsys=None):
    """Run one cell through ``run.main`` on the CPU; returns (exit code,
    parsed last line of standard output or None)."""
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import run
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    log_dir = os.environ.get("TPU_LOG_DIR")
    compilation_cache.reset_cache()
    try:
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, platforms=("cpu",))
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir
    out = capsys.readouterr().out if capsys is not None else ""
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    return rc, (json.loads(lines[-1]) if lines else None)
