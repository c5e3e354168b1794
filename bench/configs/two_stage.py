"""Plain reference of the two-stage retrieval deployments (``msmarco-rho``
and any configuration of the k knob): the same semantics as the served
path, written out directly in NumPy from the collection, one query at a
time.

It imports nothing of the program and takes nothing the program made:
scores, impacts, term statistics and features are computed here from the
collection's (doc, term, count) triples.  The one thing it reads from the
deployment is the cascade's trained forest tables, the model whose
decisions it checks.  Its float arithmetic runs in ``dtype``: float32,
as the configuration states, or a lower precision for the control.

Semantics (the configuration's guarantee): each query's class is the
first cascade node whose class-0 probability exceeds the threshold (the
last class when none does), its parameter that class's cutoff, and its
list the exact reranked top ``rerank_depth`` under that parameter:

* scores per posting: BM25 (k1 0.9, b 0.4), Dirichlet LM (mu 2500),
  TF-IDF; impacts the BM25 score quantized linearly to 0..255 over the
  collection's range;
* stage 1: each query term's postings in (impact desc, doc asc) order,
  the first ``stream_cap`` of each, merged by impact (ties keep term then
  posting order), the first ``stream_cap`` kept; the first rho of them
  summed per document (the k knob sums them all); the pool is the top
  ``rerank_depth`` (rho) or top-k (k) documents of positive score, ties
  to the lower doc id;
* stage 2: the three scores summed per document over the first
  ``stream_cap`` postings of each term, each normalized by its min and
  max over all documents, mixed with a length prior and a hash of
  (doc, request id), every weighted term rounded to 2**-20; the list is
  the pool ordered by that score, ties to the lower doc id.
"""

from __future__ import annotations

import numpy as np

K1, B, MU = 0.9, 0.4, 2500.0
LEVELS = 255
GRID = float(1 << 20)
NOISE_SEED, NOISE_W = 11, 0.35
MIX = (0.45, 0.25, 0.15)
PRIOR_W = 0.05
STATS = ("max", "q1", "q3", "min", "amean", "hmean", "median", "var", "iqr")
CHUNK = 1 << 24


def _scores(tf, df, ctf, dl, n_docs, total, avg):
    """(n, 3) float64 BM25, LM, TF-IDF of postings."""
    idf = np.log((n_docs - df + 0.5) / (df + 0.5))
    bm25 = idf * (tf * (K1 + 1.0)) / (tf + K1 * ((1.0 - B) + B * dl / avg))
    lm = np.log((tf + MU * (ctf / total)) / (dl + MU))
    tfidf = (1.0 / dl) * (1.0 + np.log(tf)) * np.log(1.0 + n_docs / df)
    return np.stack([bm25, lm, tfidf], axis=-1)


def prepare(col) -> dict:
    """Collection-wide quantities, in float64: the BM25 range that sets
    the impact quantizer, each scorer's least posting score (the term
    statistics' harmonic-mean shift) and least per-term maximum over the
    vocabulary (the features' shift; a term with no posting counts 0)."""
    df = np.bincount(col.term_ids, minlength=col.vocab).astype(np.float64)
    ctf = np.bincount(col.term_ids, weights=col.counts,
                      minlength=col.vocab)
    total = float(col.doc_len.sum())
    avg = float(col.doc_len.mean())
    lo = np.full(3, np.inf)
    hi = np.full(3, -np.inf)
    tmax = np.full((3, col.vocab), -np.inf)
    for s in range(0, len(col.term_ids), CHUNK):
        t = col.term_ids[s:s + CHUNK]
        sc = _scores(col.counts[s:s + CHUNK].astype(np.float64), df[t],
                     ctf[t], col.doc_len[col.doc_ids[s:s + CHUNK]]
                     .astype(np.float64), col.n_docs, total, avg)
        lo = np.minimum(lo, sc.min(axis=0))
        hi = np.maximum(hi, sc.max(axis=0))
        for k in range(3):
            np.maximum.at(tmax[k], t, sc[:, k])
    tmax[:, df == 0] = 0.0
    return {"score_lo": lo, "score_hi": hi, "term_max_lo": tmax.min(axis=1),
            "total": total, "avg": avg}


def _round(x, dtype):
    return np.asarray(x).astype(dtype)


def _hash_noise(docs: np.ndarray, qid: int) -> np.ndarray:
    h = ((docs.astype(np.uint32) * np.uint32(2654435761))
         ^ np.uint32((qid * 40503) & 0xFFFFFFFF) ^ np.uint32(NOISE_SEED))
    h = (h ^ (h >> np.uint32(15))) * np.uint32(2246822519)
    h = h ^ (h >> np.uint32(13))
    return (h & np.uint32(0xFFFF)).astype(np.float64) / 65536.0


def _accumulate(keys, vals, dtype):
    """Per-key sums of ``vals`` in ``dtype``, added in the given order
    (each partial sum rounded to ``dtype``).  Returns (unique keys,
    sums)."""
    uniq, inv = np.unique(keys, return_inverse=True)
    acc = np.zeros((len(uniq),) + vals.shape[1:], dtype)
    order = np.argsort(inv, kind="stable")
    first = np.searchsorted(inv[order], np.arange(len(uniq)))
    occ = np.empty(len(inv), np.int64)
    occ[order] = np.arange(len(inv)) - first[inv[order]]
    vals = vals.astype(dtype)
    for k in range(int(occ.max()) + 1 if len(occ) else 0):
        m = occ == k
        acc[inv[m]] = acc[inv[m]] + vals[m]
    return uniq, acc


class Reference:
    """The reference over the terms of ``queries``.

    ``serving`` is the configuration's ``serving`` section; ``dtype`` the
    precision of every float it computes."""

    def __init__(self, col, serving: dict, glob: dict, queries: np.ndarray,
                 dtype=np.float32):
        self.n_docs = int(col.n_docs)
        self.knob = serving["knob"]
        self.cap = int(serving["stream_cap"])
        self.depth = int(serving["rerank_depth"])
        self.cutoffs = tuple(int(c) for c in serving["cutoffs"])
        self.threshold = float(serving["threshold"])
        self.dtype = dtype
        self.doc_len = col.doc_len
        dt = dtype
        terms = np.unique(queries[queries >= 0])
        self.terms = terms
        sel = np.flatnonzero(np.isin(col.term_ids, terms))
        t = col.term_ids[sel]
        df_all = np.bincount(col.term_ids, minlength=col.vocab)
        ctf_all = np.bincount(col.term_ids, weights=col.counts,
                              minlength=col.vocab)
        dl = col.doc_len[col.doc_ids[sel]].astype(np.float64)
        sc = _round(_scores(col.counts[sel].astype(np.float64),
                            df_all[t].astype(np.float64), ctf_all[t], dl,
                            self.n_docs, glob["total"], glob["avg"]), dt)
        lo = float(_round(glob["score_lo"][0], dt))
        hi = float(_round(glob["score_hi"][0], dt))
        imp = np.round((sc[:, 0].astype(np.float64) - lo)
                       / max(hi - lo, 1e-9) * LEVELS)
        rank = np.searchsorted(terms, t).astype(np.int64)
        docs = col.doc_ids[sel].astype(np.int64)
        order = np.argsort((rank << 40) | ((LEVELS - imp.astype(np.int64))
                                           << 32) | docs)
        self.p_doc = docs[order]
        self.p_imp = imp[order].astype(dt)
        self.p_sc = sc[order]
        counts = np.bincount(rank, minlength=len(terms))
        self.off = np.concatenate([[0], np.cumsum(counts)])
        self.df = df_all[terms].astype(np.float32).astype(dt)
        self.ctf = ctf_all[terms].astype(np.float32).astype(dt)
        self.stats = self._term_stats(glob)
        self.feat_shift = _round(1.0 - _round(glob["term_max_lo"], dt)
                                 .astype(np.float64), dt)

    # -- per-term statistics and query features --------------------------
    def _term_stats(self, glob) -> np.ndarray:
        """(n_terms, 3, 9) statistics of each term's scores."""
        dt = self.dtype
        shift = 1.0 - _round(glob["score_lo"], dt).astype(np.float64)
        out = np.zeros((len(self.terms), 3, 9))
        for i in range(len(self.terms)):
            s = self.p_sc[self.off[i]:self.off[i + 1]].astype(np.float64)
            n = len(s)
            if n == 0:
                continue
            s = np.sort(s, axis=0)
            for k in range(3):
                v = s[:, k]
                amean = v.sum() / n
                var = max((v * v).sum() / n - amean ** 2, 0.0)
                hmean = n / max((1.0 / (v + shift[k])).sum(), 1e-12) \
                    - shift[k]

                def q(f):
                    return v[int(np.floor(f * (n - 1)))]

                out[i, k] = (v[-1], q(0.25), q(0.75), v[0], amean, hmean,
                             q(0.5), var, q(0.75) - q(0.25))
        return _round(out.astype(np.float32), dt)

    def features(self, queries: np.ndarray) -> np.ndarray:
        """(n, 70) features of ``queries``, in ``dtype``."""
        dt = self.dtype
        out = np.zeros((len(queries), 70), np.float64)
        for r, row in enumerate(queries):
            idx = np.searchsorted(self.terms, row[row >= 0])
            st = self.stats[idx].astype(np.float64)       # (L, 3, 9)
            f = [len(idx), self.ctf[idx].astype(np.float64).mean(),
                 self.df[idx].astype(np.float64).min(),
                 self.df[idx].astype(np.float64).max()]
            for k in range(3):
                blk = st[:, k, :]
                shift = float(self.feat_shift[k])
                inv = (1.0 / (blk[:, 0] + shift)).mean()
                f += list(blk.min(axis=0)) + list(blk.max(axis=0))
                f += [blk[:, 0].mean(), 1.0 / max(inv, 1e-12) - shift,
                      blk[:, 6].mean(), blk[:, 4].mean()]
            out[r] = f
        return _round(out.astype(np.float32), dt)

    def classes(self, queries: np.ndarray, node_params) -> np.ndarray:
        """The cascade's class of each query: the first node whose mean
        class-0 leaf probability exceeds the threshold, else the last."""
        x = self.features(queries).astype(np.float32)
        n = len(x)
        cls = np.full(n, len(node_params), np.int64)
        for i in reversed(range(len(node_params))):
            p = node_params[i]
            feat, thr = np.asarray(p["feature"]), np.asarray(p["thresh"])
            left, right = np.asarray(p["left"]), np.asarray(p["right"])
            leaf = np.asarray(p["leaf"], np.float32)
            n_trees = feat.shape[0]
            node = np.zeros((n, n_trees), np.int64)
            tr = np.arange(n_trees)[None, :]
            for _ in range(feat.shape[1]):
                f = feat[tr, node]
                xv = np.take_along_axis(x, np.maximum(f, 0), axis=1)
                go_left = (xv <= thr[tr, node]) | (f < 0)
                nxt = np.where(go_left, left[tr, node], right[tr, node])
                if np.array_equal(nxt, node):
                    break
                node = nxt
            p0 = leaf[tr, node, 0].mean(axis=1, dtype=np.float32)
            cls = np.where(p0 > np.float32(self.threshold), i, cls)
        return cls

    def width(self, cls: int) -> int:
        w = self.cutoffs[min(int(cls), len(self.cutoffs) - 1)]
        return min(w, self.cap) if self.knob == "rho" else w

    # -- stages 1 and 2 --------------------------------------------------
    def _streams(self, row):
        idx = np.searchsorted(self.terms, row[row >= 0])
        parts = [slice(self.off[i], min(self.off[i] + self.cap,
                                        self.off[i + 1])) for i in idx]
        docs = np.concatenate([self.p_doc[s] for s in parts])
        imps = np.concatenate([self.p_imp[s] for s in parts])
        sc = np.concatenate([self.p_sc[s] for s in parts])
        merged = np.argsort(-imps.astype(np.float64), kind="stable")
        merged = merged[:self.cap]
        return docs, sc, docs[merged], imps[merged]

    def query(self, row, noise_id: int, budgets) -> dict:
        """Lists of one request at each budget in ``budgets`` (rho or k).

        Returns ``lists`` {budget: (rerank_depth,) doc ids, -1 padded},
        ``stage2`` (the stage-2 score of every scored or pooled doc, as a
        doc -> score function) and ``stage1`` {budget: doc -> score}."""
        dt = self.dtype
        sdocs, sc, stream_docs, stream_imps = self._streams(row)
        # stage 2: per-scorer sums over the query's score postings
        m_docs, acc2 = _accumulate(sdocs, sc, dt)
        bounds = []
        for k in range(3):
            v = acc2[:, k]
            lo, hi = v.min(), v.max()
            if len(m_docs) < self.n_docs:           # unmatched docs hold 0
                lo, hi = min(lo, dt(0)), max(hi, dt(0))
            bounds.append((dt(lo), dt(hi)))

        def stage2(docs: np.ndarray) -> np.ndarray:
            docs = np.asarray(docs, np.int64)
            pos = np.searchsorted(m_docs, docs)
            hit = (pos < len(m_docs)) & (m_docs[np.minimum(
                pos, len(m_docs) - 1)] == docs)
            a = np.zeros((len(docs), 3), dt)
            a[hit] = acc2[pos[hit]]
            total = np.zeros(len(docs), dt)
            for k, w in enumerate(MIX):
                lo, hi = bounds[k]
                span = dt(max(hi - lo, dt(1e-9)))
                norm = ((a[:, k] - lo) / span).astype(dt)
                total = (total + self._term(w, norm)).astype(dt)
            prior = (dt(1.0) / np.log(dt(2.0) + self.doc_len[docs]
                                      .astype(np.float32).astype(dt))
                     ).astype(dt)
            total = (total + self._term(PRIOR_W, prior)).astype(dt)
            noise = _round(_hash_noise(docs, noise_id), dt)
            return (total + self._term(NOISE_W, noise)).astype(dt)

        lists, stage1 = {}, {}
        for b in budgets:
            rho = min(int(b), self.cap) if self.knob == "rho" else self.cap
            ok = stream_docs[:rho] >= 0
            d1, acc1 = _accumulate(stream_docs[:rho][ok],
                                   stream_imps[:rho][ok], dt)
            pos = acc1 > 0
            d1, acc1 = d1[pos], acc1[pos]
            order = np.lexsort((d1, -acc1.astype(np.float64)))
            depth = self.depth if self.knob == "rho" else int(b)
            pool = d1[order[:depth]]
            s2 = stage2(pool).astype(np.float64)
            ranked = pool[np.lexsort((pool, -s2))][:self.depth]
            out = np.full(self.depth, -1, np.int64)
            out[:len(ranked)] = ranked
            lists[b] = out
            stage1[b] = dict(zip(d1.tolist(), acc1.astype(np.float64)))
        return {"lists": lists, "stage2": stage2, "stage1": stage1}

    def _term(self, w, x):
        dt = self.dtype
        return (np.round((dt(w) * x).astype(dt) * dt(GRID)).astype(dt)
                / dt(GRID)).astype(dt)
