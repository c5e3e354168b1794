"""Per-kernel allclose vs the pure-jnp oracles, with shape/dtype sweeps
(interpret=True executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.embedding_bag import ops as eb_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.impact_scan import ops as is_ops
from repro.kernels.topk import ops as tk_ops

R = np.random.default_rng(42)


# ------------------------------------------------------------ flash attn --

@pytest.mark.parametrize("b,s,hq,hkv,hd", [
    (2, 64, 4, 2, 32), (1, 128, 2, 2, 16), (2, 64, 8, 1, 64),
    (1, 256, 4, 4, 32),
])
@pytest.mark.parametrize("causal,window", [
    (True, None), (False, None), (True, 16),
])
def test_flash_attention_sweep(b, s, hq, hkv, hd, causal, window):
    q = jnp.asarray(R.normal(size=(b, s, hq, hd)).astype(np.float32))
    k = jnp.asarray(R.normal(size=(b, s, hkv, hd)).astype(np.float32))
    v = jnp.asarray(R.normal(size=(b, s, hkv, hd)).astype(np.float32))
    out = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 block_q=32, block_kv=32, interpret=True)
    ref = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 use_kernel=False, interpret=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-5), ("bfloat16", 2e-2)])
def test_flash_attention_dtypes(dtype, tol):
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    q = jnp.asarray(R.normal(size=(1, 64, 4, 32))).astype(dt)
    k = jnp.asarray(R.normal(size=(1, 64, 2, 32))).astype(dt)
    v = jnp.asarray(R.normal(size=(1, 64, 2, 32))).astype(dt)
    out = fa_ops.flash_attention(q, k, v, block_q=32, block_kv=32,
                                 interpret=True)
    ref = fa_ops.flash_attention(q, k, v, use_kernel=False, interpret=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


# ------------------------------------------------------------ impact scan --

@pytest.mark.parametrize("q,p,nd,rho,bp,bd", [
    (3, 300, 500, 100, 64, 128),
    (2, 1024, 2048, 1024, 256, 512),
    (1, 100, 77, 33, 32, 32),
    (2, 128, 64, 0, 32, 64),      # rho = 0: nothing scored
    (1, 64, 128, 1000, 32, 64),   # rho > P: everything scored
])
def test_impact_scan_sweep(q, p, nd, rho, bp, bd):
    docs = jnp.asarray(R.integers(-1, nd, (q, p)).astype(np.int32))
    imps = jnp.asarray((R.random((q, p)) * 255).astype(np.float32))
    a = is_ops.saat_accumulate(docs, imps, n_docs=nd, rho=rho,
                               block_p=bp, block_d=bd, interpret=True)
    b = is_ops.saat_accumulate(docs, imps, n_docs=nd, rho=rho,
                               use_kernel=False, interpret=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_impact_scan_rho_semantics():
    """Kernel must process exactly the first rho stream entries."""
    docs = jnp.asarray(np.array([[0, 1, 2, 3]], np.int32))
    imps = jnp.asarray(np.array([[10., 20., 30., 40.]], np.float32))
    a = np.asarray(is_ops.saat_accumulate(docs, imps, n_docs=4, rho=2,
                                          block_p=2, block_d=2,
                                          interpret=True))
    assert list(a[0]) == [10.0, 20.0, 0.0, 0.0]


def _int_streams(q, p, nd, seed=7):
    """Quantized-impact streams (integer-valued f32, like the index
    produces) — partial sums are exact, so kernel vs oracle comparisons
    can demand bit-identity, not allclose."""
    r = np.random.default_rng(seed)
    docs = jnp.asarray(r.integers(-1, nd, (q, p)).astype(np.int32))
    imps = jnp.asarray(r.integers(0, 256, (q, p)).astype(np.float32))
    return docs, imps


@pytest.mark.parametrize("q,p,nd,bp,bd", [
    (4, 300, 500, 64, 128),
    (3, 128, 77, 32, 32),
    (2, 65, 40, 32, 16),          # ragged stream tail (65 % 32 != 0)
])
def test_impact_scan_traced_rho_mixed(q, p, nd, bp, bd):
    """Per-query traced rho, including rho=0 and rho>P, is bit-identical
    to the masked oracle — one executable, every rho bucket."""
    docs, imps = _int_streams(q, p, nd)
    rho = jnp.asarray(
        np.array([0, 1, p // 2, p + 50][:q], np.int32))
    a = is_ops.saat_accumulate(docs, imps, n_docs=nd, rho=rho,
                               block_p=bp, block_d=bd, interpret=True)
    b = is_ops.saat_accumulate(docs, imps, n_docs=nd, rho=rho,
                               use_kernel=False, interpret=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rho", [0, 1, 33, 100, 1000])
def test_impact_scan_constant_rho_bit_identical_to_ref(rho):
    """Acceptance: a constant rho vector reproduces the static-rho
    oracle bit for bit."""
    from repro.kernels.impact_scan.ref import impact_scan_ref

    docs, imps = _int_streams(3, 100, 200)
    rho_vec = jnp.full((3,), rho, jnp.int32)
    a = is_ops.saat_accumulate(docs, imps, n_docs=200, rho=rho_vec,
                               block_p=32, block_d=64, interpret=True)
    ref = impact_scan_ref(docs, imps, n_docs=200, rho=rho)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ref))


def test_impact_scan_segment_skips_fewer_cells():
    """Segment metadata turns the dense grid sparse: doc-clustered
    posting blocks execute only intersecting doc tiles, the executed-cell
    counter matches the analytic predicate, and the output is unchanged."""
    from repro.kernels.impact_scan.kernel import live_cell_count
    from repro.retrieval.index import block_doc_bounds

    q, p, nd, bp, bd = 3, 128, 512, 32, 64
    r = np.random.default_rng(3)
    # each posting block's docs cluster into one doc tile
    blocks = []
    for pb in range(p // bp):
        base = (pb * 131) % (nd - bd)
        blocks.append(r.integers(base, base + bd, (q, bp)))
    docs = jnp.asarray(np.concatenate(blocks, axis=1).astype(np.int32))
    imps = jnp.asarray(r.integers(0, 256, (q, p)).astype(np.float32))
    rho = jnp.asarray([0, 50, 128], jnp.int32)
    seg = block_doc_bounds(docs, block_p=bp, n_docs=nd)

    dense, cnt_dense = is_ops.saat_accumulate(
        docs, imps, n_docs=nd, rho=rho, block_p=bp, block_d=bd,
        with_stats=True, interpret=True)
    skip, cnt_skip = is_ops.saat_accumulate(
        docs, imps, n_docs=nd, rho=rho, block_p=bp, block_d=bd,
        seg_bounds=seg, with_stats=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(skip))
    analytic = int(live_cell_count(rho, *seg, p=p, n_docs=nd,
                                   block_p=bp, block_d=bd))
    assert int(np.asarray(cnt_skip).sum()) == analytic
    assert analytic < int(np.asarray(cnt_dense).sum())
    # rho=0 query executes nothing at all
    assert int(np.asarray(cnt_skip)[0].sum()) == 0


def test_impact_scan_exhausted_stream_blocks_skipped():
    """Blocks that are pure padding carry the empty interval and never
    execute — rho beyond the live stream costs nothing extra."""
    from repro.retrieval.index import block_doc_bounds

    docs = jnp.asarray(
        np.concatenate([np.array([[3, 1, 2, 0]], np.int32),
                        np.full((1, 12), -1, np.int32)], axis=1))
    imps = jnp.asarray(np.full((1, 16), 5.0, np.float32))
    seg = block_doc_bounds(docs, block_p=4, n_docs=8)
    rho = jnp.asarray([16], jnp.int32)
    acc, cnt = is_ops.saat_accumulate(docs, imps, n_docs=8, rho=rho,
                                      block_p=4, block_d=8,
                                      seg_bounds=seg, with_stats=True,
                                      interpret=True)
    assert int(np.asarray(cnt).sum()) == 1      # only the live block ran
    assert list(np.asarray(acc)[0, :4]) == [5.0, 5.0, 5.0, 5.0]


def test_impact_scan_rho_zero_skips_kernel_launch(monkeypatch):
    """Static rho=0 returns zeros without touching pallas_call."""
    def boom(*a, **k):
        raise AssertionError("kernel launched for rho=0")

    monkeypatch.setattr("repro.kernels.impact_scan.ops._kernel", boom)
    docs, imps = _int_streams(2, 32, 40)
    out = is_ops.saat_accumulate(docs, imps, n_docs=40, rho=0, interpret=True)
    assert np.asarray(out).shape == (2, 40) and not np.asarray(out).any()
    out, cnt = is_ops.saat_accumulate(docs, imps, n_docs=40, rho=0,
                                      with_stats=True, interpret=True)
    assert not np.asarray(out).any() and not np.asarray(cnt).any()


def test_impact_scan_validation_errors():
    docs, imps = _int_streams(2, 32, 40)
    with pytest.raises(ValueError, match="rho must be >= 0"):
        is_ops.saat_accumulate(docs, imps, n_docs=40, rho=-1, interpret=True)
    with pytest.raises(ValueError, match="integer dtype"):
        is_ops.saat_accumulate(docs, imps, n_docs=40,
                               rho=jnp.asarray([1.0, 2.0]), interpret=True)
    with pytest.raises(ValueError, match="shaped"):
        is_ops.saat_accumulate(docs, imps, n_docs=40,
                               rho=jnp.asarray([1, 2, 3], jnp.int32),
                               interpret=True)
    with pytest.raises(ValueError, match="segment bounds"):
        bad = jnp.zeros((2, 7), jnp.int32)
        is_ops.saat_accumulate(docs, imps, n_docs=40,
                               rho=jnp.asarray([1, 2], jnp.int32),
                               block_p=8, seg_bounds=(bad, bad),
                               interpret=True)


def test_oracle_with_stats_matches_kernel_counts():
    """The oracle path now supports with_stats: the analytic predicate
    sum must equal what the kernel actually measures, per doc block."""
    from repro.retrieval.index import block_doc_bounds

    q, p, nd, bp, bd = 3, 64, 128, 16, 32
    docs, imps = _int_streams(q, p, nd)
    rho = jnp.asarray([0, 20, 64], jnp.int32)
    seg = block_doc_bounds(docs, block_p=bp, n_docs=nd)
    acc_k, cnt_k = is_ops.saat_accumulate(
        docs, imps, n_docs=nd, rho=rho, block_p=bp, block_d=bd,
        seg_bounds=seg, with_stats=True, interpret=True)
    acc_o, cnt_o = is_ops.saat_accumulate(
        docs, imps, n_docs=nd, rho=rho, block_p=bp, block_d=bd,
        seg_bounds=seg, with_stats=True, use_kernel=False, interpret=False)
    np.testing.assert_array_equal(np.asarray(acc_k), np.asarray(acc_o))
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_o))
    # and without seg bounds both synthesize the same full-range bounds
    _, cd_k = is_ops.saat_accumulate(docs, imps, n_docs=nd, rho=rho,
                                     block_p=bp, block_d=bd,
                                     with_stats=True, interpret=True)
    _, cd_o = is_ops.saat_accumulate(docs, imps, n_docs=nd, rho=rho,
                                     block_p=bp, block_d=bd,
                                     with_stats=True, use_kernel=False,
                                     interpret=False)
    np.testing.assert_array_equal(np.asarray(cd_k), np.asarray(cd_o))


# ------------------------------------------------------------------ topk --

@pytest.mark.parametrize("q,n,k,bn", [
    (2, 1000, 10, 256), (1, 5000, 64, 512), (3, 300, 128, 128),
    (1, 257, 7, 64),
])
def test_topk_sweep(q, n, k, bn):
    s = jnp.asarray(R.normal(size=(q, n)).astype(np.float32))
    v1, i1 = tk_ops.topk_select(s, k, block_n=bn, interpret=True)
    v2, i2 = tk_ops.topk_select(s, k, use_kernel=False, interpret=False)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2))


def test_block_topk_rejects_invalid_kp():
    """kp outside [1, 128] must raise, never return a silently-wrong
    union (per-block top-kp only contains the global top-k for k <= kp)."""
    from repro.kernels.topk.kernel import KP_MAX, block_topk

    s = jnp.asarray(R.normal(size=(2, 512)).astype(np.float32))
    for kp in (0, -3, KP_MAX + 1, 500):
        with pytest.raises(ValueError, match=r"kp must be in \[1, 128\]"):
            block_topk(s, kp=kp, block_n=256, interpret=True)
    # topk_select no longer falls back silently: the kernel path raises
    # past KP_MAX, and pool_route sends such widths to the sort path,
    # which serves them exactly (checked against lax.top_k, not against
    # its own code path)
    from repro.retrieval import topk as topk_lib

    with pytest.raises(ValueError, match=r"kp must be in \[1, 128\]"):
        tk_ops.topk_select(s, KP_MAX + 50, interpret=True)
    assert topk_lib.pool_route(KP_MAX, use_kernel=True) == "pallas"
    assert topk_lib.pool_route(KP_MAX + 50, use_kernel=True) == "xla"
    assert topk_lib.pool_route(10, use_kernel=False) == "xla"
    v1, i1 = tk_ops.topk_select(s, KP_MAX + 50, use_kernel=False,
                                interpret=False)
    vr, ir = jax.lax.top_k(s, KP_MAX + 50)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(vr))


def test_topk_ties_prefer_low_index():
    s = jnp.asarray(np.array([[1.0, 5.0, 5.0, 0.0, 5.0]], np.float32))
    _, idx = tk_ops.topk_select(s, 3, block_n=2, interpret=True)
    assert list(np.asarray(idx)[0]) == [1, 2, 4]


_LAX_TOP_K = jax.lax.top_k


def _top_k_highest_index_ties(x, k):
    """``lax.top_k`` with equal values highest index first — a backend
    whose tie order is not XLA:CPU's."""
    n = x.shape[-1]
    v, i = _LAX_TOP_K(x[..., ::-1], k)
    return v, n - 1 - i


@pytest.mark.parametrize("backend_ties", ["lowest", "highest"])
@pytest.mark.parametrize("q,n,k,levels", [
    (3, 500, 40, 4), (2, 1000, 200, 16), (1, 64, 64, 2), (4, 300, 1, 3),
])
def test_top_k_lowest_index_any_backend(monkeypatch, backend_ties, q, n, k,
                                        levels):
    """Integer-valued scores (many ties, as JASS accumulators have) select
    and order exactly as the lexsort oracle, whatever tie order the
    backend's ``lax.top_k`` has; a smaller k is a prefix of a larger."""
    from repro.kernels.topk.ref import top_k_lowest_index, topk_ref

    x = jnp.asarray(R.integers(0, levels, (q, n)).astype(np.float32))
    vr, ir = topk_ref(x, k)
    if backend_ties == "highest":
        monkeypatch.setattr(jax.lax, "top_k", _top_k_highest_index_ties)
        # the stand-in really reorders ties, or the case checks nothing
        assert not np.array_equal(np.asarray(jax.lax.top_k(x, k)[1]),
                                  np.asarray(ir))
    v, i = top_k_lowest_index(x, k)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    _, i_half = top_k_lowest_index(x, max(1, k // 2))
    np.testing.assert_array_equal(np.asarray(i_half),
                                  np.asarray(ir)[:, :max(1, k // 2)])


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.integers(5, 200), st.integers(1, 16))
def test_topk_property(q, n, k):
    k = min(k, n)
    s = jnp.asarray(np.random.default_rng(q * n + k)
                    .normal(size=(q, n)).astype(np.float32))
    v1, i1 = tk_ops.topk_select(s, k, block_n=32, interpret=True)
    v2, i2 = tk_ops.topk_select(s, k, use_kernel=False, interpret=False)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


# --------------------------------------------------------- embedding bag --

@pytest.mark.parametrize("v,d,b,l,comb", [
    (100, 16, 8, 5, "sum"), (50, 8, 4, 3, "mean"), (30, 32, 16, 1, "sum"),
    (200, 64, 2, 7, "mean"),
])
def test_embedding_bag_sweep(v, d, b, l, comb):
    t = jnp.asarray(R.normal(size=(v, d)).astype(np.float32))
    ids = jnp.asarray(R.integers(-1, v, (b, l)).astype(np.int32))
    o1 = eb_ops.embedding_bag(t, ids, combiner=comb, interpret=True)
    o2 = eb_ops.embedding_bag(t, ids, combiner=comb, use_kernel=False,
                              interpret=False)
    # kernel accumulates slots strictly left-to-right; the jnp oracle's
    # sum may reduce in a different order -> allow one-ULP slack
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5,
                               atol=1e-6)


def test_embedding_bag_all_padding():
    t = jnp.asarray(R.normal(size=(10, 4)).astype(np.float32))
    ids = jnp.full((2, 3), -1, jnp.int32)
    o = eb_ops.embedding_bag(t, ids, combiner="mean", interpret=True)
    assert np.allclose(np.asarray(o), 0.0)


def test_embedding_bag_matches_model_layer():
    from repro.models.recsys import embedding as E

    t = jnp.asarray(R.normal(size=(40, 8)).astype(np.float32))
    ids = jnp.asarray(R.integers(-1, 40, (6, 4)).astype(np.int32))
    np.testing.assert_allclose(
        np.asarray(eb_ops.embedding_bag(t, ids, interpret=True)),
        np.asarray(E.bag_fixed(t, ids)), rtol=1e-5, atol=1e-6)
