"""Closed itemsets by LCM with deferred PPC checks: the plain reference.

The algorithm of `src/repro/core/lcm.py`: a stack of nodes (occurrence
set, core item, prefix count); popping a node counts the support of every
item in its occurrence set, takes the closure {j : s[j] == support}, drops
the node when its closure holds a different number of items before the core
than its parent's did (the PPC check), and pushes every item after the core
that is outside the closure and frequent.  Every accepted node is one
distinct closed itemset.

What differs from that file is how the work is batched: up to `batch` nodes
are popped at once, their supports come from one float32 product of 0/1
rows (exact while there are fewer than 2**24 transactions), and the checks
run on whole arrays.  Occurrence sets are packed bits (`np.packbits`,
little-endian).  The order in which nodes are visited changes; the family of
closed itemsets and every support do not, and neither does LAMP's final
lambda (see `lamp.py`).

`count_dtype` carries every support through a narrower type before it is
compared or reported.  The reference never sets it; the benchmark's control
sets bfloat16, so that supports above 256 round.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

__all__ = ["ClosedSets", "lcm_closed", "pack_rows", "popcount_rows"]


def pack_rows(bool_rows: np.ndarray) -> np.ndarray:
    """[..., N] bool -> [..., ceil(N/8)] uint8, bit t of a row in byte t//8."""
    return np.packbits(np.asarray(bool_rows, dtype=bool), axis=-1, bitorder="little")


def popcount_rows(packed: np.ndarray) -> np.ndarray:
    return np.bitwise_count(packed).sum(axis=-1, dtype=np.int64)


@dataclass
class ClosedSets:
    """Closed itemsets as rows: `closure` [K, ceil(M/8)] packed item bits,
    `support` [K], `pos_support` [K] when labels were given, and `occ`
    [K, ceil(N/8)] packed occurrence sets when asked for."""

    closure: np.ndarray
    support: np.ndarray
    pos_support: np.ndarray | None = None
    occ: np.ndarray | None = None

    def __len__(self) -> int:
        return len(self.support)


def lcm_closed(
    db_bool: np.ndarray,
    min_sup: int = 1,
    *,
    labels: np.ndarray | None = None,
    keep_occ: bool = False,
    dynamic_min_sup: Callable[[int], int] | None = None,
    batch: int = 256,
    count_dtype=None,
) -> ClosedSets:
    """All closed itemsets of `db_bool` [N, M] with support >= min_sup.

    dynamic_min_sup(support) returns the (possibly raised) threshold after
    each closed itemset: LAMP's support increase.  Raising the threshold
    in the middle of a batch prunes no less soundly than between nodes.
    """
    db = np.asarray(db_bool, dtype=bool)
    n, m = db.shape
    if n >= 2**24:
        raise ValueError(f"{n} transactions: float32 supports are exact below 2**24")
    db_f = db.astype(np.float32)
    cols = pack_rows(db.T)  # [M, N/8]
    pos_bits = None if labels is None else pack_rows(labels)
    items = np.arange(m)

    def counts(x):
        return x if count_dtype is None else x.astype(count_dtype).astype(np.float64)

    lam = min_sup
    out_clo, out_sup, out_pos, out_occ = [], [], [], []
    nb = cols.shape[1]
    st_occ = np.empty((1024, nb), np.uint8)
    st_core = np.empty(1024, np.int64)
    st_pc = np.empty(1024, np.int64)
    st_occ[0], st_core[0], st_pc[0] = pack_rows(np.ones(n, bool)), -1, 0
    top = 1
    while top:
        lo = max(0, top - batch)
        occ, core, pc = st_occ[lo:top].copy(), st_core[lo:top].copy(), st_pc[lo:top].copy()
        top = lo
        sup = counts(popcount_rows(occ))
        live = sup >= lam
        occ, core, pc, sup = occ[live], core[live], pc[live], sup[live]
        if not len(sup):
            continue
        rows = np.unpackbits(occ, axis=-1, count=n, bitorder="little")
        s = counts(rows.astype(np.float32) @ db_f)  # [B, M]
        in_clo = s == sup[:, None]
        cum = np.cumsum(in_clo, axis=1)
        before = np.where(core > 0, cum[np.arange(len(core)), np.maximum(core - 1, 0)], 0)
        ok = (core < 0) | (before == pc)  # PPC: duplicates from other parents go
        occ, core, sup, in_clo, cum, s = occ[ok], core[ok], sup[ok], in_clo[ok], cum[ok], s[ok]
        if not len(sup):
            continue
        out_clo.append(np.packbits(in_clo, axis=1, bitorder="little"))
        out_sup.append(sup.astype(np.int64))
        if pos_bits is not None:
            out_pos.append(popcount_rows(occ & pos_bits))
        if keep_occ:
            out_occ.append(occ)
        if dynamic_min_sup is not None:
            for v in sup:
                lam = max(lam, int(dynamic_min_sup(int(v))))
        r, e = np.nonzero(~in_clo & (s >= lam) & (items[None, :] > core[:, None]))
        if not len(r):
            continue
        r, e = r[::-1], e[::-1]  # each node's smallest extension ends on top
        c = len(r)
        if top + c > len(st_core):
            size = max(2 * len(st_core), top + c)
            st_occ = np.resize(st_occ, (size, nb))
            st_core = np.resize(st_core, size)
            st_pc = np.resize(st_pc, size)
        st_occ[top:top + c] = occ[r] & cols[e]
        st_core[top:top + c] = e
        st_pc[top:top + c] = np.where(e > 0, cum[r, np.maximum(e - 1, 0)], 0)
        top += c
    def cat(parts, shape, dtype):
        return np.concatenate(parts) if parts else np.zeros(shape, dtype)

    return ClosedSets(
        closure=cat(out_clo, (0, (m + 7) // 8), np.uint8),
        support=cat(out_sup, 0, np.int64),
        pos_support=cat(out_pos, 0, np.int64) if pos_bits is not None else None,
        occ=cat(out_occ, (0, nb), np.uint8) if keep_occ else None,
    )
