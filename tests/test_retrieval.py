"""Retrieval substrate: index vs brute force, JASS semantics, gold runs."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import features as feat_lib
from repro.retrieval import corpus as corpus_lib
from repro.retrieval import gold, index as index_lib, jass, scoring, topk
from repro.serving import engine as engine_lib


@pytest.fixture(scope="module")
def small():
    c = corpus_lib.make_corpus(corpus_lib.CorpusConfig(
        n_docs=400, vocab=900, mean_doc_len=60, seed=11))
    idx = index_lib.build_index(c)
    q = corpus_lib.make_queries(c, n_queries=32, seed=12)
    return c, idx, q


def test_index_stats_match_bruteforce(small):
    c, idx, _ = small
    # rebuild df/ctf from raw corpus
    df = np.bincount(c.term_ids, minlength=c.config.vocab)
    ctf = np.bincount(c.term_ids, weights=c.counts, minlength=c.config.vocab)
    assert np.array_equal(idx.term_stats.df, df.astype(np.float32))
    assert np.allclose(idx.term_stats.ctf, ctf)


def test_bm25_scores_match_manual(small):
    c, idx, _ = small
    col = idx.collection
    t = int(c.term_ids[0])
    sl = idx.postings_of(t)
    docs = idx.postings_doc[sl]
    tfs = idx.postings_tf[sl].astype(np.float64)
    dlen = c.doc_len[docs].astype(np.float64)
    df = float(idx.term_stats.df[t])
    manual = np.asarray(scoring.bm25(tfs, df, dlen, col))
    assert np.allclose(idx.postings_score[sl, 0], manual, rtol=1e-5)


def test_impact_order_descending_within_term(small):
    _, idx, _ = small
    for t in np.unique(idx.corpus.term_ids)[:50]:
        sl = idx.postings_of(int(t))
        imp = idx.postings_impact[sl].astype(np.int32)
        assert (np.diff(imp) <= 0).all()


def test_stream_gather_complete(small):
    """The merged stream must contain every posting of the query terms
    (cap large enough), in impact-descending order."""
    _, idx, q = small
    offs = jnp.asarray(idx.offsets)
    ds, im = jass.gather_streams(offs, jnp.asarray(idx.postings_doc),
                                 jnp.asarray(idx.postings_impact
                                             .astype(np.float32)),
                                 jnp.asarray(q.terms[:8]), cap=400)
    ds, im = np.asarray(ds), np.asarray(im)
    assert (np.diff(im, axis=1) <= 1e-6).all()
    for qi in range(8):
        want = 0
        for t in q.terms[qi]:
            if t >= 0:
                sl = idx.postings_of(int(t))
                want += sl.stop - sl.start
        got = int((ds[qi] >= 0).sum())
        assert got == min(want, 400)


def test_saat_exhaustive_matches_bruteforce(small):
    c, idx, q = small
    offs = jnp.asarray(idx.offsets)
    ds, im = jass.gather_streams(offs, jnp.asarray(idx.postings_doc),
                                 jnp.asarray(idx.postings_impact
                                             .astype(np.float32)),
                                 jnp.asarray(q.terms[:4]), cap=400)
    acc = np.asarray(jass.saat_scores(ds, im, c.n_docs, 400))
    for qi in range(4):
        manual = np.zeros(c.n_docs)
        for t in q.terms[qi]:
            if t >= 0:
                sl = idx.postings_of(int(t))
                np.add.at(manual, idx.postings_doc[sl],
                          idx.postings_impact[sl].astype(np.float64))
        assert np.allclose(acc[qi], manual, atol=1e-3)


def test_saat_rho_monotone(small):
    c, idx, q = small
    offs = jnp.asarray(idx.offsets)
    ds, im = jass.gather_streams(offs, jnp.asarray(idx.postings_doc),
                                 jnp.asarray(idx.postings_impact
                                             .astype(np.float32)),
                                 jnp.asarray(q.terms[:8]), cap=256)
    prev = None
    for rho in (8, 32, 128, 256):
        acc = np.asarray(jass.saat_scores(ds, im, c.n_docs, rho))
        if prev is not None:
            assert (acc >= prev - 1e-6).all()   # impacts are nonnegative
        prev = acc


def test_topk_is_safe(small):
    c, idx, q = small
    offs = jnp.asarray(idx.offsets)
    ds, im = jass.gather_streams(offs, jnp.asarray(idx.postings_doc),
                                 jnp.asarray(idx.postings_impact
                                             .astype(np.float32)),
                                 jnp.asarray(q.terms[:4]), cap=400)
    pool = np.asarray(topk.candidates_topk(ds, im, c.n_docs, 10))
    scores = np.asarray(topk.exhaustive_scores(ds, im, c.n_docs))
    for qi in range(4):
        order = np.lexsort((np.arange(c.n_docs), -scores[qi]))
        want = [d for d in order[:10] if scores[qi, d] > 0]
        got = [d for d in pool[qi] if d >= 0]
        assert got == want


def test_candidate_run_is_restriction(small):
    """B_k must be gold's ranking restricted to the top-k pool."""
    c, idx, q = small
    offs = jnp.asarray(idx.offsets)
    ds, im = jass.gather_streams(offs, jnp.asarray(idx.postings_doc),
                                 jnp.asarray(idx.postings_impact
                                             .astype(np.float32)),
                                 jnp.asarray(q.terms[:4]), cap=400)
    acc = jass.saat_scores(ds, im, c.n_docs, 400)
    pool = jass.rank_from_scores(acc, 50)
    stage2 = gold.second_stage_scores(acc, acc, acc,
                                      jnp.asarray(c.doc_len),
                                      jnp.arange(4))
    a = np.asarray(gold.gold_run_k(stage2, pool, 30))
    b = np.asarray(gold.candidate_run_k(stage2, pool, 10, 30))
    for qi in range(4):
        pool_k = set(np.asarray(pool)[qi, :10].tolist()) - {-1}
        got = [d for d in b[qi] if d >= 0]
        want = [d for d in a[qi] if d in pool_k]
        # A is truncated at depth 30, so B's tail may extend past A's
        # coverage — the overlapping prefix must match exactly
        assert got[:len(want)] == want
        assert set(got) <= pool_k


def test_features_shape_and_padding(small):
    _, idx, q = small
    stats = jnp.asarray(idx.term_stats.stats)
    ctf = jnp.asarray(idx.term_stats.ctf)
    df = jnp.asarray(idx.term_stats.df)
    f = feat_lib.query_features(jnp.asarray(q.terms), stats, ctf, df)
    assert f.shape == (q.n_queries, feat_lib.N_FEATURES)
    assert not bool(jnp.any(jnp.isnan(f)))
    assert len(feat_lib.feature_names()) == 70
    # padding invariance: extending the pad columns must not change feats
    wider = np.concatenate(
        [q.terms, np.full((q.n_queries, 3), -1, np.int32)], axis=1)
    f2 = feat_lib.query_features(jnp.asarray(wider), stats, ctf, df)
    assert np.allclose(np.asarray(f), np.asarray(f2), atol=1e-5)


def test_packed_key_sorts_match_lexsort():
    """The index build's packed-key sorts give the lexsorts' results:
    (term, score) order over negative, zero and positive float32 scores,
    and the (term, -impact, doc) posting order."""
    r = np.random.default_rng(5)
    n, vocab = 5000, 300
    term_of = r.integers(0, vocab, n).astype(np.int64)
    scores = np.concatenate([r.normal(size=n - 4) * 7,
                             [0.0, -0.5, 3e-39, -3e-39]]).astype(np.float32)
    s, t = index_lib._sort_by_term(scores, term_of)
    order = np.lexsort((scores, term_of))
    np.testing.assert_array_equal(t, term_of[order])
    np.testing.assert_array_equal(s, scores[order])

    pairs = np.unique(r.integers(0, vocab * 900, n))    # unique (term, doc)
    term_of, doc_ids = (pairs // 900).astype(np.int64), (pairs % 900)
    impact = r.integers(0, 256, len(pairs)).astype(np.uint8)
    np.testing.assert_array_equal(
        index_lib._impact_order(term_of, impact, doc_ids.astype(np.int32),
                                255, vocab),
        np.lexsort((doc_ids, -impact.astype(np.int32), term_of)))


def test_rank_from_scores_ties_prefer_low_doc_id():
    """top_k-based ranking keeps the (score desc, doc id asc) order of a
    lexsort, drops zero scores, and clamps depth to the doc count."""
    s = jnp.asarray(np.array([[0., 2., 5., 2., 5., 0., 1.],
                              [0., 0., 0., 0., 0., 0., 0.]], np.float32))
    got = np.asarray(jass.rank_from_scores(s, 5))
    np.testing.assert_array_equal(got, [[2, 4, 1, 3, 6], [-1] * 5])
    assert jass.rank_from_scores(s, 50).shape == (2, 7)


def test_second_stage_scores_identical_eager_and_jitted():
    """Stage-2 scores do not depend on how the program around them was
    fused: the per-bucket reference evaluates them op by op, the engine
    inside one jitted stage, and the rankings must agree bit for bit."""
    r = np.random.default_rng(9)
    q, n = 8, 20_000
    accs = [jnp.asarray((r.random((q, n)) * 30).astype(np.float32))
            for _ in range(3)]
    doc_len = jnp.asarray(r.integers(5, 300, n).astype(np.int32))
    qids = jnp.arange(q, dtype=jnp.int32)
    eager = gold.second_stage_scores(*accs, doc_len, qids)
    jitted = jax.jit(gold.second_stage_scores)(*accs, doc_len, qids)
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))


def _score_postings(r, *, q, n_terms, cap, n_docs, span, pad, lm_neg):
    """Gathered score postings as ``gather_score_streams`` lays them out:
    term t's postings in columns [t*cap, (t+1)*cap), a doc at most once
    per term, -1 padded at the end of a term's segment; docs drawn from
    the first ``span`` ids, so the terms of a query share documents."""
    docs = np.full((q, n_terms, cap), -1, np.int32)
    for i in range(q):
        for t in range(n_terms):
            n = cap - (r.integers(0, cap // 2) if pad else 0)
            if pad and t == n_terms - 1:
                n = 0                               # a padded query slot
            docs[i, t, :n] = r.choice(span, n, replace=False)
    s3 = np.stack([r.random((q, n_terms, cap)) * 9,           # bm25 > 0
                   -r.random((q, n_terms, cap)) * 4 if lm_neg
                   else r.normal(size=(q, n_terms, cap)),
                   r.random((q, n_terms, cap)) * 3], axis=-1)
    s3 = np.where(docs[..., None] >= 0, s3, 0.0).astype(np.float32)
    return docs.reshape(q, -1), s3.reshape(q, n_terms * cap, 3)


@pytest.mark.parametrize("case", [
    dict(n_docs=300, span=40, pad=False, lm_neg=False, k=20),  # shared docs
    dict(n_docs=300, span=40, pad=True, lm_neg=False, k=20),   # -1 padding
    dict(n_docs=300, span=40, pad=False, lm_neg=True, k=20),   # LM sums < 0
    dict(n_docs=300, span=300, pad=True, lm_neg=False, k=60),  # unscored pool
    dict(n_docs=16, span=16, pad=False, lm_neg=False, k=12),   # all touched
    dict(n_docs=300, span=120, pad=True, lm_neg=True, k=300),  # max_k wide
], ids=["shared", "padding", "lm_negative", "unscored_pool",
        "all_touched", "k_pool"])
def test_pool_stage2_equals_dense(case):
    """Stage 2 over the pool alone gives the dense stage-2 scores at the
    pool's docs bit for bit, and the same reranked lists, whatever the
    postings hold: docs under several terms, padding, docs with no score
    posting, negative sums, and every doc touched (0 outside the bounds)."""
    r = np.random.default_rng(31)
    q, n_terms, cap, n_docs = 6, 4, 16, case["n_docs"]
    sdocs, s3 = _score_postings(r, q=q, n_terms=n_terms, cap=cap,
                                n_docs=n_docs, span=case["span"],
                                pad=case["pad"], lm_neg=case["lm_neg"])
    touched = [len(set(row[row >= 0].tolist())) for row in sdocs]
    if case["span"] == n_docs and not case["pad"]:
        assert min(touched) == n_docs      # the bounds leave out 0
    pool = np.stack([r.choice(n_docs, case["k"], replace=False)
                     for _ in range(q)]).astype(np.int32)
    if case["pad"]:
        pool[:, case["k"] // 2:] = -1
        pool[-1] = -1                      # an empty pool
    doc_len = jnp.asarray(r.integers(5, 300, n_docs).astype(np.int32))
    qids = jnp.arange(100, 100 + q, dtype=jnp.int32)
    args = (jnp.asarray(sdocs), jnp.asarray(s3))

    @jax.jit
    def dense(sd, sc):
        return gold.second_stage_scores(
            *jass.scorer_accumulators(sd, sc, n_docs), doc_len, qids)

    sparse = jax.jit(functools.partial(gold.pool_stage2_scores,
                                       n_docs=n_docs, cap=cap))
    full = np.asarray(dense(*args))
    got = np.asarray(sparse(*args, jnp.asarray(pool), doc_len, qids))
    want = np.take_along_axis(full, np.clip(pool, 0, None), axis=1)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(gold.rerank_scored(got, pool, 30)),
        np.asarray(gold.rerank_pool(full, pool, 30)))


def _slot_table(r, *, slots, cap, n_docs, span, pad, ties):
    """A slot table as the scheduler leaves it: each slot's doc stream
    (docs drawn with repeats from the first ``span`` ids, as a doc listed
    under several terms recurs in the merged stream; -1 padded at the end
    with ``pad``; slot 0 empty) and its accumulator row, the sum of
    the stream's integer impacts up to a budget, zero at every other doc.
    ``ties`` draws impacts from {1, 2, 3}, so equal sums are common."""
    ds = np.full((slots, cap), -1, np.int32)
    acc = np.zeros((slots, n_docs), np.float32)
    for s in range(1, slots):
        n = cap - (r.integers(0, cap // 2) if pad else 0)
        ds[s, :n] = r.choice(span, n)
        im = r.integers(1, 4 if ties else 256, n).astype(np.float32)
        end = r.integers(n // 2, n + 1)             # the budget
        np.add.at(acc[s], ds[s, :end], im[:end])
    return ds, acc


@pytest.mark.parametrize("body", ["select", "rho", "k"])
@pytest.mark.parametrize("case", [
    dict(route="xla", width=24, cap=64, span=40, pad=False, ties=True),
    dict(route="pallas", width=24, cap=64, span=40, pad=True, ties=True),
    dict(route="xla", width=200, cap=64, span=300, pad=True, ties=False),
    dict(route="pallas", width=100, cap=64, span=300, pad=True,
         ties=False),
    dict(route="xla", width=40, cap=256, span=600, pad=True, ties=True),
], ids=["xla_cut_ties", "pallas_cut_ties", "xla_cap_below_width",
        "pallas_cap_below_width", "xla_sparse"])
def test_stream_pool_select_equals_dense(case, body):
    """The pool select over a slot's stream documents gives the dense
    ``select_pool(acc[slot_idx])`` bit for bit, and both knobs' finalize
    bodies give the lists of the dense select: docs repeated in the
    stream, equal sums straddling the cut (the lower doc id wins), fewer
    positive docs than ``width`` (a -1 tail), a stream narrower than the
    pool, -1 padding, an empty stream, and a padded group entry
    (== capacity) on both routes."""
    r = np.random.default_rng(17)
    slots, grain, n_docs, n_terms = 12, 8, 700, 3
    width, cap, route = case["width"], case["cap"], case["route"]
    ds, acc = _slot_table(r, slots=slots, cap=cap, n_docs=n_docs,
                          span=case["span"], pad=case["pad"],
                          ties=case["ties"])
    slot_idx = np.concatenate(
        [r.choice(np.arange(1, slots), grain - 2, replace=False),
         [0, slots]]).astype(np.int32)     # empty, padding (clamps)
    rows = acc[np.minimum(slot_idx, slots - 1)]
    top = -np.sort(-rows, axis=1)
    if case["ties"] and width < cap:
        assert ((top[:, width - 1] == top[:, width])
                & (top[:, width] > 0)).any()        # a tie at the cut
    if width > cap:
        assert ((top > 0).sum(axis=1) < width).all()
    acc_d, ds_d, idx_d = map(jnp.asarray, (acc, ds, slot_idx))
    dense = topk.select_pool(acc_d[idx_d], width, route=route,
                             interpret=True)
    if body == "select":
        got = topk.select_pool_from_stream(acc_d, idx_d, ds_d[idx_d], width,
                                           route=route, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(dense))
        assert (np.asarray(got)[-2] == -1).all()    # the empty stream
        return
    sdocs, s3 = _score_postings(r, q=slots, n_terms=n_terms, cap=cap,
                                n_docs=n_docs, span=n_docs, pad=True,
                                lm_neg=False)
    doc_len = jnp.asarray(r.integers(5, 300, n_docs).astype(np.int32))
    qids = jnp.arange(50, 50 + grain, dtype=jnp.int32)
    dvec = jnp.asarray(r.integers(1, 30, grain).astype(np.int32))
    depth = width if body == "rho" else 30
    pool = dense
    if body == "k":
        k_vec = jnp.asarray(r.integers(1, width + 1, grain).astype(np.int32))
        pool = jnp.where(jnp.arange(width)[None, :] < k_vec[:, None],
                         dense, -1)
    scores = gold.pool_stage2_scores(
        jnp.asarray(sdocs)[idx_d], jnp.asarray(s3)[idx_d], pool, doc_len,
        qids, n_docs=n_docs, cap=cap)
    want = gold.rerank_scored(scores, engine_lib._depth_mask(pool, dvec),
                              depth)
    common = dict(depth=depth, n_docs=n_docs, cap=cap, route=route,
                  interpret=True)
    tables = (acc_d, ds_d, jnp.asarray(sdocs), jnp.asarray(s3), idx_d)
    if body == "rho":
        got = jax.jit(functools.partial(engine_lib._sched_finalize_rho,
                                        **common))(
            *tables, dvec, qids, doc_len)
    else:
        got = jax.jit(functools.partial(engine_lib._sched_finalize_k,
                                        max_k=width, **common))(
            *tables, k_vec, dvec, qids, doc_len)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_second_stage_mix_shared_columns_equal_per_row():
    """The mixture over a block whose rows share their columns (the dense
    and sharded callers: 1-D ids and lengths) equals the per-row form
    (the pool callers: (Q, K) ids and lengths) bit for bit."""
    r = np.random.default_rng(4)
    q, w = 5, 700
    accs = [jnp.asarray(r.normal(size=(q, w)).astype(np.float32))
            for _ in range(3)]
    bounds = tuple((jnp.min(a, axis=1, keepdims=True),
                    jnp.max(a, axis=1, keepdims=True)) for a in accs)
    ids = jnp.asarray(r.integers(0, 10**6, w).astype(np.int32))
    dl = jnp.asarray(r.integers(5, 300, w).astype(np.int32))
    qids = jnp.arange(q, dtype=jnp.int32)
    shared = gold.second_stage_mix(*accs, bounds, dl, qids, ids)
    per_row = gold.second_stage_mix(
        *accs, bounds, jnp.broadcast_to(dl, (q, w)), qids,
        jnp.broadcast_to(ids, (q, w)))
    np.testing.assert_array_equal(np.asarray(shared), np.asarray(per_row))


def test_scorer_sums_refuses_an_overflowing_key():
    docs = jnp.zeros((1, 4 * 8), jnp.int32)
    with pytest.raises(ValueError, match="int32 sort key"):
        jass.scorer_sums(docs, jnp.zeros((1, 32, 3)), 2**29, 8)
