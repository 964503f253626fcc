"""The plain reference: the edge set at a commit timestamp, rebuilt from
the base edges and the acknowledged writes' stamps, and a numpy float64
PageRank over it.  Imports nothing of the store and takes nothing it made:
the base edges and the writes come from the generator, the stamps are the
values ``ticket.wait()`` returned."""

from __future__ import annotations

import numpy as np

from .gen import in_sorted, split_keys


class EdgeHistory:
    """Which edges are live at a timestamp, when no edge is touched twice.

    ``ins_keys``/``del_keys`` are each write's inserted and deleted keys,
    ``stamps`` the commit timestamp of each write (0: never committed)."""

    def __init__(self, base_keys, ins_keys, del_keys, stamps):
        stamps = np.asarray(stamps, np.int64)
        self.base = base_keys
        self.ins, self.ins_ts = self._flatten(ins_keys, stamps)
        self.dels, self.del_ts = self._flatten(del_keys, stamps)

    @staticmethod
    def _flatten(keys, stamps):
        keys = np.asarray(keys, np.int64)
        ts = np.repeat(stamps, keys.shape[1]) if keys.ndim == 2 else stamps
        keys = keys.reshape(-1)
        committed = ts > 0
        keys, ts = keys[committed], ts[committed]
        order = np.argsort(keys)
        return keys[order], ts[order]

    def _stamp(self, keys, table, table_ts):
        """Commit stamp of each key in ``table``, or 0."""
        out = np.zeros(len(keys), np.int64)
        if len(table) == 0:
            return out
        i = np.minimum(np.searchsorted(table, keys), len(table) - 1)
        hit = table[i] == keys
        out[hit] = table_ts[i[hit]]
        return out

    def live(self, keys, ts) -> np.ndarray:
        """Whether each key is an edge at timestamp ``ts`` (scalar or per key)."""
        keys = np.asarray(keys, np.int64)
        ts = np.broadcast_to(np.asarray(ts, np.int64), keys.shape)
        d = self._stamp(keys, self.dels, self.del_ts)
        i = self._stamp(keys, self.ins, self.ins_ts)
        in_base = in_sorted(keys, self.base)
        return (in_base & ~((d > 0) & (d <= ts))) | ((i > 0) & (i <= ts))

    def edges_at(self, ts: int) -> np.ndarray:
        """Sorted keys of the edge set at ``ts``."""
        gone = self.dels[(self.del_ts > 0) & (self.del_ts <= ts)]
        added = self.ins[(self.ins_ts > 0) & (self.ins_ts <= ts)]
        mask = np.ones(len(self.base), bool)
        mask[np.searchsorted(self.base, gone)] = False  # deletes are base edges
        keep = self.base[mask]
        return np.insert(keep, np.searchsorted(keep, added), added)


def pagerank(keys, n, iters=10, damping=0.85) -> np.ndarray:
    """Push PageRank in float64 over the sorted edge keys ``keys``:
    ``iters`` iterations, the dangling mass spread evenly."""
    import scipy.sparse as sp

    src, dst = split_keys(keys)
    indptr = np.searchsorted(src, np.arange(n + 1))
    deg = np.diff(indptr).astype(np.float64)
    a_t = sp.csr_matrix((np.ones(len(dst)), dst, indptr), shape=(n, n)).T
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    p = np.full(n, 1.0 / n)
    for _ in range(iters):
        agg = a_t @ (p * inv)
        dangling = p[deg == 0].sum()
        p = (1.0 - damping) / n + damping * (agg + dangling / n)
    return p


def max_rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))
