"""One-sided Fisher exact test and Tarone's bound, in numpy and scipy.

A copy of the host half of `src/repro/stats/fisher.py`.  `dtype` is float64
for the reference; the benchmark's control computes the same formulas in
float32, the precision below the one the configurations state.
"""

from __future__ import annotations

import numpy as np
from scipy.special import gammaln

__all__ = ["fisher_pvalue", "lamp_count_thresholds", "log_comb",
           "min_attainable_pvalue"]


def log_comb(n, k, dtype=np.float64):
    """log C(n, k), -inf where k < 0 or k > n.  Vectorized."""
    n = np.asarray(n, dtype=dtype)
    k = np.asarray(k, dtype=dtype)
    valid = (k >= 0) & (k <= n)
    kk = np.where(valid, k, dtype(0))
    out = gammaln(n + 1) - gammaln(kk + 1) - gammaln(n - kk + 1)
    return np.where(valid, out, dtype(-np.inf)).astype(dtype)


def fisher_pvalue(x, n, N, N_pos, dtype=np.float64, chunk: int = 2048):
    """P[#positives >= n | margins] under the hypergeometric null, over
    same-shape arrays x (support) and n (positive support), `chunk` at a time."""
    x = np.atleast_1d(np.asarray(x, dtype=np.int64))
    n = np.atleast_1d(np.asarray(n, dtype=np.int64))
    if len(x) > chunk:  # chunks of like support keep each [chunk, K] table small
        order = np.argsort(x, kind="stable")
        out = np.empty(len(x), dtype=dtype)
        for i in range(0, len(x), chunk):
            sel = order[i:i + chunk]
            out[sel] = fisher_pvalue(x[sel], n[sel], N, N_pos, dtype)
        return out
    hi = np.minimum(x, N_pos)
    max_hi = int(hi.max()) if hi.size else 0
    ni = np.arange(max_hi + 1)[None, :]
    mask = (ni >= n[:, None]) & (ni <= hi[:, None])
    logp = (log_comb(N_pos, ni, dtype) + log_comb(N - N_pos, x[:, None] - ni, dtype)
            - log_comb(N, x, dtype)[:, None])
    logp = np.where(mask, logp, dtype(-np.inf))
    m = np.max(logp, axis=1, keepdims=True)
    m = np.where(np.isfinite(m), m, dtype(0))
    p = np.exp(m[:, 0]) * np.sum(np.exp(logp - m), axis=1)
    return np.clip(p, 0.0, 1.0).astype(dtype)


def min_attainable_pvalue(x, N, N_pos):
    """Tarone's f(x): the least P-value any itemset of support x can reach."""
    x = np.asarray(x, dtype=np.int64)
    n_star = np.minimum(x, N_pos)
    logf = log_comb(N_pos, n_star) + log_comb(N - N_pos, x - n_star) - log_comb(N, x)
    return np.exp(np.clip(logf, -745.0, 0.0))


def lamp_count_thresholds(N, N_pos, alpha):
    """thr[lam] = alpha / f(lam - 1) for lam = 0..N+1, frozen past N_pos + 1."""
    lam = np.arange(N + 2)
    f = min_attainable_pvalue(np.maximum(lam - 1, 0), N, N_pos)
    thr = alpha / np.maximum(f, 1e-300)
    cap = min(N_pos + 1, N + 1)
    thr[cap + 1:] = np.inf
    return thr
