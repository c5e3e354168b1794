"""CPU rehearsal of chip_smoke.py: its serve-and-compare path at a tiny
size (both knobs x both lifecycles against the per-bucket reference), its
``--four-chips`` path on four emulated CPU devices, and its refusal to run
without a TPU.  The test calls the script's functions
itself, past the platform check, so the script needs no option for it."""

import importlib.util
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

TINY = dict(n_docs=600, mean_doc_len=40.0, stream_cap=256, n_queries=128,
            query_batch=32, pool_depth=200, gold_depth=50)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_path_matches_reference_at_tiny_size(smoke, capsys):
    smoke.run_one_chip(TINY, seed=3, n_batches=2, expect_compiled=False)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    phases = {x["phase"]: x for x in lines}
    for knob in ("rho", "k"):
        for lifecycle in ("batch-once", "continuous"):
            label = f"{knob}/{lifecycle}"
            assert phases[f"compare:{label}"]["bit_identical"] is True
            assert phases[f"serve:{label}"]["requests"] == 2 * smoke.BATCH
            # warmup compiled everything serving needs
            assert phases[f"serve:{label}"]["compiles"] == 0
        warm = phases[f"warmup:{knob}/continuous"]
        routes = set(warm["topk_routes"].values())
        # off the kernel path every pool is selected by XLA; on it, the
        # k knob's 200-wide pool is past the kernel's 128
        if not warm["use_kernel"]:
            assert routes == {"xla"}
        else:
            assert routes == {"pallas" if knob == "rho" else "xla"}


_FOUR_DEVICES = textwrap.dedent("""
    import importlib.util, json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    smoke.run_four_chips(json.loads(sys.argv[1]), seed=3, n_batches=1,
                         expect_compiled=False)
""")


def test_four_chip_path_matches_one_chip_at_tiny_size():
    """``--four-chips``' path on four emulated CPU devices, in a child so
    this session keeps one device: every sharded ranked list (both knobs,
    both lifecycles) equals the one-device engine's, and the doc-range
    arrays really are spread over the four devices."""
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICES,
                        json.dumps(TINY)], cwd=ROOT, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    phases = {x["phase"]: x for x in map(json.loads, r.stdout.splitlines())}
    for knob in ("rho", "k"):
        server = phases[f"server:{knob}/sharded"]
        assert server["mesh"] == {"data": 1, "model": 4}
        assert server["doc_len_devices"] == 4
        for lifecycle in ("batch-once", "continuous"):
            label = f"{knob}/sharded-{lifecycle}"
            assert phases[f"compare:{label}"]["bit_identical"] is True
            assert phases[f"serve:{label}"]["compiles"] == 0


def test_check_engine_refuses_interpreted_kernels(smoke):
    class Eng:
        use_kernel, interpret = True, True

    with pytest.raises(smoke.SmokeFailure, match="interpret=True"):
        smoke.check_engine(Eng(), expect_compiled=True)
    smoke.check_engine(Eng(), expect_compiled=False)


def test_main_refuses_cpu_and_names_the_platform(smoke, capsys):
    assert smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    assert '"ok"' not in out
