"""Plain numpy reference of MFTune's propose step, for the benchmark's check.

It imports nothing of the program under test. Its inputs are data:

* each source forest as the raw node arrays of its trees (feature, threshold,
  left, right, leaf mean, leaf variance) and the training targets the trees
  were fitted to;
* the candidate pool in unit space;
* each source's incumbent and weight.

It computes, in the floating-point type it is given (float64 is the
reference; float32 is the control that the check has to refuse):

* each tree's leaf, by a plain descent: left when ``x <= threshold``;
* the probabilistic random forest's mean and variance: the trees' means
  averaged, the variance the mean of the leaf variances plus the variance of
  the leaf means, floored at 1e-10, both put back into the targets' units
  with the targets' mean and (population) standard deviation;
* expected improvement for minimisation against the source's incumbent, the
  variance floored at 1e-12, values below the smallest normal number of the
  type set to 0;
* each source's ranks (0 for the highest improvement, ties in candidate
  order) and their sum weighted by the source weights;
* the k candidates with the lowest aggregate, ties in candidate order.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np
from scipy.special import ndtr

VAR_FLOOR = 1e-10
EI_VAR_FLOOR = 1e-12


def leaf_stats(forest: Dict, X: np.ndarray, dtype=np.float64):
    """Each tree's leaf mean and variance for the rows of ``X``, (T, N)
    each, in ``dtype``. ``forest`` holds ``trees`` (a list of dicts of node
    arrays) and ``y`` (the training targets)."""
    X = np.asarray(X, dtype=dtype)
    n = len(X)
    rows = np.arange(n)
    ms, vs = [], []
    for t in forest["trees"]:
        feat = np.asarray(t["feature"])
        thr = np.asarray(t["threshold"], dtype=dtype)
        left, right = np.asarray(t["left"]), np.asarray(t["right"])
        nid = np.zeros(n, dtype=np.int64)
        while True:
            f = feat[nid]
            inner = f >= 0
            if not inner.any():
                break
            r = rows[inner]
            node = nid[inner]
            go_left = X[r, f[inner]] <= thr[node]
            nid[r] = np.where(go_left, left[node], right[node])
        ms.append(np.asarray(t["mean"], dtype=dtype)[nid])
        vs.append(np.asarray(t["var"], dtype=dtype)[nid])
    return np.stack(ms), np.stack(vs)


def forest_predict(forest: Dict, X: np.ndarray, dtype=np.float64, stats=None):
    """(mean, var) of one forest on the rows of ``X``, in ``dtype`` (from
    its ``leaf_stats``, when given)."""
    m, v = leaf_stats(forest, X, dtype) if stats is None else stats
    y = np.asarray(forest["y"], dtype=np.float64)
    y_mean = dtype(y.mean())
    y_std = dtype(y.std() or 1.0)
    mean = m.mean(axis=0, dtype=dtype)
    var = np.maximum(v.mean(axis=0, dtype=dtype) + m.var(axis=0, dtype=dtype),
                     dtype(VAR_FLOOR))
    return mean * y_std + y_mean, var * y_std * y_std


def expected_improvement(mean, var, best, dtype=np.float64):
    """EI for minimisation, E[max(best - y, 0)] with y ~ N(mean, var)."""
    mean = np.asarray(mean, dtype=dtype)
    var = np.asarray(var, dtype=dtype)
    std = np.sqrt(np.maximum(var, dtype(EI_VAR_FLOOR)))
    diff = dtype(best) - mean
    z = diff / std
    phi = np.exp(dtype(-0.5) * z * z) / dtype(np.sqrt(2.0 * np.pi))
    val = np.maximum(diff * ndtr(z).astype(dtype) + std * phi, dtype(0.0))
    return np.where(val < np.finfo(dtype).tiny, dtype(0.0), val).astype(dtype)


def aggregate_ranks(scores: np.ndarray, weights: Sequence[float],
                    dtype=np.float64) -> np.ndarray:
    """Sum over sources of weight x rank (rank 0 = highest score)."""
    S, N = scores.shape
    agg = np.zeros(N, dtype=dtype)
    for s in range(S):
        order = np.argsort(-scores[s], kind="stable")
        rank = np.empty(N, dtype=dtype)
        rank[order] = np.arange(N, dtype=dtype)
        agg = agg + dtype(weights[s]) * rank
    return agg


def score_pool(forests: Sequence[Dict], X: np.ndarray,
               incumbents: Sequence[float], weights: Sequence[float],
               dtype=np.float64) -> np.ndarray:
    """The aggregate rank of every candidate of the pool ``X``."""
    scores = np.stack([
        expected_improvement(*forest_predict(f, X, dtype), inc, dtype)
        for f, inc in zip(forests, incumbents)
    ])
    return aggregate_ranks(scores, weights, dtype)


def top_k(agg: np.ndarray, k: int) -> np.ndarray:
    return np.argsort(agg, kind="stable")[:k]

