"""Compile-only checks for a described TPU v5e: the served path's Pallas
kernels at one chip's deployment widths (Q=64 queries, P=4096 postings,
2,210,456 docs, blocks 512/2048).

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, which raises what the chip's compiler would refuse (block
shapes off the (8, 128) tiling, scoped-VMEM overruns).  The topology is
described inside a module fixture — never at import — so every xdist
worker collects the same tests and only the one that runs them loads the
TPU compiler."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.impact_scan.kernel import impact_scan, posting_blocks
from repro.kernels.topk import ops as tk_ops
from repro.serving import engine as engine_lib

Q, P, N_DOCS = 64, 4096, 2_210_456
BLOCK_P, BLOCK_D = 512, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


@pytest.fixture(scope="module")
def no_cache():
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of any cache a caller configured
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _stream_args(spec):
    _, n_p = posting_blocks(P, BLOCK_P)
    return (spec((Q, P), jnp.int32), spec((Q, P), jnp.float32),
            spec((Q,), jnp.int32), spec((Q, n_p), jnp.int32),
            spec((Q, n_p), jnp.int32))


@pytest.mark.parametrize("with_stats", [False, True])
def test_impact_scan_compiles_for_v5e(spec, no_cache, with_stats):
    fn = jax.jit(lambda *a: impact_scan(
        *a, n_docs=N_DOCS, block_p=BLOCK_P, block_d=BLOCK_D,
        with_stats=with_stats, interpret=False))
    compiled = fn.lower(*_stream_args(spec)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the (Q, n_docs) f32 accumulator is the output, not a blow-up of it
    assert mem.output_size_in_bytes < 2 * Q * N_DOCS * 4


def test_topk_select_compiles_for_v5e(spec, no_cache):
    fn = jax.jit(lambda s: tk_ops.topk_select(s, 100, interpret=False))
    compiled = fn.lower(spec((Q, N_DOCS), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stage1_rho_compiles_for_v5e(spec, no_cache):
    """The engine's jitted stage-1 body under the rho knob: traced-rho
    impact_scan, then pool selection on the "pallas" route."""
    import functools
    body = functools.partial(
        engine_lib._stage1_rho, n_docs=N_DOCS, depth=100, use_kernel=True,
        interpret=False, block_p=BLOCK_P, block_d=BLOCK_D, route="pallas")
    ds, im, rho, seg_lo, seg_hi = _stream_args(spec)
    compiled = jax.jit(body).lower(ds, im, seg_lo, seg_hi, rho).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_stage1_k_compiles_for_v5e(spec, no_cache):
    """The k knob's stage 1: impact_scan, then the 2,000-wide shared pool
    on the "xla" route (``top_k_lowest_index``), within one chip."""
    import functools
    body = functools.partial(
        engine_lib._stage1_k, n_docs=N_DOCS, max_k=2000, use_kernel=True,
        interpret=False, block_p=BLOCK_P, block_d=BLOCK_D, route="xla")
    ds, im, k_vec, seg_lo, seg_hi = _stream_args(spec)
    compiled = jax.jit(body).lower(ds, im, seg_lo, seg_hi, k_vec).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # a 16 GiB chip also holds the ~2.2 GiB index and stage 2's ~5.3 GiB
    # of temporaries: stage 1's own must stay a few accumulators
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def _widest_sort_or_kernel(compiled) -> int:
    """The widest dimension of any operand or result of a sort or a
    Pallas call in a compiled program's text."""
    widest = 0
    for line in compiled.as_text().splitlines():
        if " sort(" in line or "tpu_custom_call" in line:
            dims = re.findall(r"\[([\d,]+)\]", line)
            widest = max([widest] + [int(d) for g in dims
                                     for d in g.split(",") if d])
    return widest


def test_sched_finalize_rho_compiles_without_dense_stage2(spec, no_cache):
    """The scheduler's finalize at the benchmark cell's widths (a group
    of 8 of 64 slots, 16 query terms x 4,096 score postings, 4,420,912
    docs, depth 100): the pool select reads the slots' 4,096 stream docs,
    so no sort or Pallas call spans the docs, and stage 2 runs over the
    gathered postings, so what it adds to the temporaries of the pool
    selection stays below the (8, n_docs, 3) f32 block a dense stage 2
    builds."""
    import functools
    from repro.retrieval import topk as topk_lib
    slots, grain, n_terms, n_docs = 64, 8, 16, 4_420_912
    body = functools.partial(
        engine_lib._sched_finalize_rho, depth=100, n_docs=n_docs, cap=P,
        route="pallas", interpret=False)
    acc, slot_idx = spec((slots, n_docs), jnp.float32), spec((grain,),
                                                             jnp.int32)
    ds = spec((slots, P), jnp.int32)
    args = (acc, ds, spec((slots, n_terms * P), jnp.int32),
            spec((slots, n_terms * P, 3), jnp.float32), slot_idx,
            spec((grain,), jnp.int32), spec((grain,), jnp.int32),
            spec((n_docs,), jnp.int32))
    compiled = jax.jit(body).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _widest_sort_or_kernel(compiled) < n_docs
    select = jax.jit(lambda a, d, i: topk_lib.select_pool_from_stream(
        a, i, d[i], 100, route="pallas", interpret=False)).lower(
            acc, ds, slot_idx).compile()
    stage2_temp = (compiled.memory_analysis().temp_size_in_bytes
                   - select.memory_analysis().temp_size_in_bytes)
    assert stage2_temp < grain * n_docs * 3 * 4


#: the k deployment's widths (``bench/configs/msmarco-k.json``): 64 slots,
#: refill groups of 8, 16 query terms, stream cap 16,384, 4,420,912 docs
K_SLOTS, K_GRAIN, K_TERMS, K_CAP, K_DOCS = 64, 8, 16, 16_384, 4_420_912


def test_sched_finalize_k_fits_beside_the_deployment(spec, no_cache):
    """The k knob's finalize at the deployment's widths: a group of 8
    rows, ``L·cap`` 262,144 score postings a row, the 10,000-wide pool
    select on the "xla" route over the slots' 16,384 stream docs (no
    sort spans the 4,420,912 docs), depth 100.  A served run of this
    deployment peaks at 11.4-14.7 GB of the v5e's 16.9 GB.  The
    program's temporaries compile to ~0.16 GB, most of them stage 2's
    (8, 262,144) key sort; a select over the docs held ~0.57 GB."""
    import functools
    body = functools.partial(
        engine_lib._sched_finalize_k, depth=100, max_k=10_000,
        n_docs=K_DOCS, cap=K_CAP, route="xla", interpret=False)
    lp = K_TERMS * K_CAP
    vec = spec((K_GRAIN,), jnp.int32)
    args = (spec((K_SLOTS, K_DOCS), jnp.float32),
            spec((K_SLOTS, K_CAP), jnp.int32),
            spec((K_SLOTS, lp), jnp.int32),
            spec((K_SLOTS, lp, 3), jnp.float32), vec, vec, vec, vec,
            spec((K_DOCS,), jnp.int32))
    compiled = jax.jit(body).lower(*args).compile()
    assert _widest_sort_or_kernel(compiled) < K_DOCS
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28


def test_sched_refill_gathers_compile_at_the_k_stream_cap(spec, no_cache):
    """The refill's stream gathers and slot install at stream cap 16,384:
    ``(8, 16 × 16,384)`` = 2,097,152 score postings a group.  A served
    run of this deployment peaks at 11.4-14.7 GB of the v5e's 16.9 GB,
    so temporaries beyond 1 GiB would not fit beside the rest."""
    import functools
    # postings of the order of the 4,420,912-passage shard's index
    nnz, vocab, bounds_p = 150_000_000, 2_660_824, BLOCK_P
    gather = functools.partial(engine_lib._sched_gather, cap=K_CAP,
                               bounds_p=bounds_p, n_docs=K_DOCS,
                               with_bounds=True)
    g = jax.jit(gather).lower(
        spec((vocab + 1,), jnp.int32), spec((nnz,), jnp.int32),
        spec((nnz,), jnp.float32), spec((nnz, 3), jnp.float32),
        spec((K_GRAIN, K_TERMS), jnp.int32)).compile()
    lp, nb = K_TERMS * K_CAP, K_CAP // bounds_p

    def table(n):
        return (spec((n, K_CAP), jnp.int32), spec((n, K_CAP), jnp.float32),
                spec((n, nb), jnp.int32), spec((n, nb), jnp.int32),
                spec((n, lp), jnp.int32), spec((n, lp, 3), jnp.float32))

    r = jax.jit(engine_lib._sched_refill).lower(
        *table(K_SLOTS), spec((K_SLOTS, K_DOCS), jnp.float32),
        spec((K_GRAIN,), jnp.int32), *table(K_GRAIN)).compile()
    for compiled in (g, r):
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
