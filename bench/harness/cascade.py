"""The deployment's cascade, labelled and fitted here from the reference.

The program serves the cascade and the reference checks its decisions;
neither reads tables the program trained.  A training query's class is
the first cutoff whose list, as the plain reference computes it, lies
within the MED-RBP envelope ``tau`` of the same query's list at the
largest cutoff (the last class when none does), the measure
``in_envelope_pct`` applies to served requests.  Node i is a random
forest that answers "does cutoff i suffice?" (label 0 where the class is
at most i), fitted on the reference's features of the training log.

The forest follows ``repro.core.forest.train_forest`` (bootstrap
aggregated trees of Gini splits over quantile-binned features, a share
of the features tried at each node), copied so that a change to the
program's training cannot change the deployment the benchmark serves.
Its tables are ``feature``, ``thresh``, ``left``, ``right`` (T, N) and
``leaf`` (T, N, 2): a leaf has feature -1 and points at itself.  One
departure: a split's threshold lies midway between the training values
it separates, not on a quantile that is itself a training value.  The
training queries split alike, and a served query that shares a term
statistic with one of them does not sit on the threshold, where the
float32 rounding of the program and of the reference could send it
either way.
"""

from __future__ import annotations

import numpy as np

from harness.check import med_rbp

__all__ = ["label", "fit_forest", "fit_cascade"]


def label(ref, queries: np.ndarray, cutoffs, *, rbp_p: float,
          tau: float) -> np.ndarray:
    """(n,) class of each training query; row i keys its stage-2 noise
    on id i."""
    top = int(max(cutoffs))
    out = np.full(len(queries), len(cutoffs), np.int64)
    for i, row in enumerate(queries):
        lists = ref.query(row, i, sorted(set(int(c) for c in cutoffs)))
        for j, c in enumerate(cutoffs):
            if med_rbp(lists["lists"][int(c)], lists["lists"][top],
                       rbp_p) <= tau:
                out[i] = j
                break
    return out


def _gini_gain(hist_l: np.ndarray, hist_r: np.ndarray) -> np.ndarray:
    nl = hist_l.sum(-1)
    nr = hist_r.sum(-1)
    n = nl + nr
    gl = 1.0 - ((hist_l / np.maximum(nl[:, None], 1)) ** 2).sum(-1)
    gr = 1.0 - ((hist_r / np.maximum(nr[:, None], 1)) ** 2).sum(-1)
    tot = hist_l + hist_r
    gp = 1.0 - ((tot / np.maximum(n[:, None], 1)) ** 2).sum(-1)
    gain = gp - (nl / np.maximum(n, 1)) * gl - (nr / np.maximum(n, 1)) * gr
    gain[(nl == 0) | (nr == 0)] = -1.0
    return gain


def _cuts(x: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """(F, B) threshold of each split: a value ``t`` with every training
    value below the edge at most ``t`` and every other one above it, the
    midpoint of the two nearest where float32 holds one between them."""
    cuts = edges.copy()
    for f in range(x.shape[1]):
        xs = np.unique(x[:, f])
        hi = np.searchsorted(xs, edges[f], side="left")
        ok = (hi > 0) & (hi < len(xs))
        lo_v = xs[np.maximum(hi - 1, 0)]
        hi_v = xs[np.minimum(hi, len(xs) - 1)]
        mid = ((lo_v.astype(np.float64) + hi_v) / 2).astype(np.float32)
        mid = np.where(mid < hi_v, mid, lo_v)
        cuts[f] = np.where(ok, mid, edges[f])
    return cuts


def _fit_tree(xb, y, edges, rng, max_depth: int, feat_frac: float,
              min_leaf: int) -> list:
    n, n_feat = xb.shape
    bins = edges.shape[1] + 1
    m = max(1, int(round(feat_frac * n_feat)))
    nodes: list[dict] = []

    def leaf(idx):
        hist = np.bincount(y[idx], minlength=2).astype(np.float64)
        nodes.append({"feature": -1, "thresh": 0.0, "left": len(nodes),
                      "right": len(nodes), "leaf": hist / max(hist.sum(), 1)})
        return len(nodes) - 1

    def grow(idx, depth):
        if depth >= max_depth or len(idx) < 2 * min_leaf or \
                len(np.unique(y[idx])) == 1:
            return leaf(idx)
        best = (-1.0, None, None)
        for f in rng.choice(n_feat, size=m, replace=False):
            h = np.zeros((bins, 2))
            np.add.at(h, (xb[idx, f], y[idx]), 1.0)
            cum = np.cumsum(h, axis=0)
            gain = _gini_gain(cum[:-1], cum[-1][None, :] - cum[:-1])
            b = int(np.argmax(gain))
            if gain[b] > best[0]:
                best = (float(gain[b]), int(f), b)
        if best[1] is None or best[0] <= 1e-12:
            return leaf(idx)
        _, f, b = best
        go_l = xb[idx, f] <= b
        li, ri = idx[go_l], idx[~go_l]
        if len(li) < min_leaf or len(ri) < min_leaf:
            return leaf(idx)
        nid = len(nodes)
        nodes.append({"feature": f, "thresh": float(edges[f, b]), "left": -1,
                      "right": -1, "leaf": np.zeros(2)})
        nodes[nid]["left"] = grow(li, depth + 1)
        nodes[nid]["right"] = grow(ri, depth + 1)
        return nid

    grow(np.arange(n), 0)
    return nodes


def fit_forest(x: np.ndarray, y: np.ndarray, *, n_trees: int, max_depth: int,
               seed: int, bins: int = 32, feat_frac: float = 0.3,
               min_leaf: int = 8) -> dict:
    """A binary random forest's tables."""
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.int64)
    n, n_feat = x.shape
    qs = np.linspace(0, 1, bins + 1)[1:-1]
    edges = np.quantile(x, qs, axis=0).T.astype(np.float32)
    edges = np.maximum.accumulate(edges + np.arange(bins - 1) * 1e-12, axis=1)
    xb = np.stack([np.searchsorted(edges[f], x[:, f], side="right")
                   for f in range(n_feat)], axis=1).astype(np.int64)
    cuts = _cuts(x, edges)
    rng = np.random.default_rng(seed)
    trees = []
    for _ in range(n_trees):
        boot = rng.integers(0, n, size=n)
        trees.append(_fit_tree(xb[boot], y[boot], cuts, rng, max_depth,
                               feat_frac, min_leaf))
    width = max(len(t) for t in trees)
    out = {"feature": np.full((n_trees, width), -1, np.int32),
           "thresh": np.zeros((n_trees, width), np.float32),
           "left": np.zeros((n_trees, width), np.int32),
           "right": np.zeros((n_trees, width), np.int32),
           "leaf": np.zeros((n_trees, width, 2), np.float32)}
    out["leaf"][:, :, 0] = 1.0
    for t, tree in enumerate(trees):
        for i, nd in enumerate(tree):
            for k in out:
                out[k][t, i] = nd[k]
    return out


def fit_cascade(x: np.ndarray, classes: np.ndarray, n_cutoffs: int, *,
                n_trees: int, max_depth: int, seed: int) -> list:
    """One forest per cutoff: node i labels a query 0 where its class is
    at most i."""
    return [fit_forest(x, (classes > i).astype(np.int64), n_trees=n_trees,
                       max_depth=max_depth, seed=seed + i)
            for i in range(n_cutoffs)]
