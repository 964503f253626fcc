"""Public wrapper for batched leaf search: pads to tile multiples, picks the
kernel on TPU and interpret mode elsewhere."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...obs.trace import TRACER as _trc
from ..runtime import device_cache_enabled, use_interpret
from .kernel import leaf_search_kernel
from .ref import leaf_search_ref

SENTINEL = np.int32(np.iinfo(np.int32).max)


def leaf_search(rows, targets, q_block: int = 256):
    """Batched Search(u, v): locate targets[i] in sorted padded rows[i].

    rows: [Q, B] int32 (B padded to 128-multiple by the caller's layout),
    targets: [Q] int32. Returns (found [Q] bool, pos [Q] int32).
    """
    rows = jnp.asarray(rows, jnp.int32)
    targets = jnp.asarray(targets, jnp.int32)
    q, b = rows.shape
    if q == 0:
        return jnp.zeros(0, bool), jnp.zeros(0, jnp.int32)
    qb = min(q_block, max(8, q))
    pad_q = (-q) % qb
    if pad_q:
        rows = jnp.pad(rows, ((0, pad_q), (0, 0)), constant_values=SENTINEL)
        targets = jnp.pad(targets, (0, pad_q))
    found, pos = leaf_search_kernel(rows, targets, q_block=qb, interpret=use_interpret())
    return found[:q], pos[:q]


def edge_search_view(view, us, vs, q_block: int = 256) -> np.ndarray:
    """Batched edge-membership Search(u, v) through the device tile cache.

    Resolves each query's candidate tiles via the host block index (the
    delta-plane assembler memoizes both the spliced block stream and its
    src-sorted order on the view), gathers those rows *on device* — the leaf
    blocks themselves are never re-uploaded — and answers every query with
    one batched ``leaf_search``: query i hits iff any tile of ``us[i]``
    contains ``vs[i]``.  Returns a bool [len(us)] numpy array.
    """
    from repro.core import view_assembler

    us = np.asarray(us, np.int64).reshape(-1)
    vs = np.asarray(vs, np.int64).reshape(-1)
    if us.shape != vs.shape:
        raise ValueError("us and vs must have matching shapes")
    src, order = view_assembler.block_src_index(view)
    lo = np.searchsorted(src[order], us, "left")
    hi = np.searchsorted(src[order], us, "right")
    counts = hi - lo
    out = np.zeros(len(us), bool)
    if counts.sum() == 0:
        return out
    qidx = np.repeat(np.arange(len(us)), counts)
    flat = np.concatenate([order[l:h] for l, h in zip(lo, hi) if h > l])
    if device_cache_enabled():
        dev = view.to_leaf_blocks_device()
        if getattr(dev, "groups", None) is not None:
            # tiered tiles: route each candidate leaf to its tier group and
            # run one fixed-[*, B_t] batched search per tier
            tiers = view.to_leaf_stream().leaf_tiers
            cand_t = tiers[flat]
            for t in dev.tiers:
                m = cand_t == t
                if not m.any():
                    continue
                pos = np.searchsorted(dev.gidx[t], flat[m])
                with _trc.site("leaf_gather"):
                    rows_sel = dev.groups[t][1][jnp.asarray(pos, jnp.int32)]
                with _trc.site("leaf_search"):
                    found, _ = leaf_search(
                        rows_sel, jnp.asarray(vs[qidx[m]], jnp.int32),
                        q_block=q_block,
                    )
                np.logical_or.at(out, qidx[m], np.asarray(found))
            return out
        with _trc.site("leaf_gather"):
            rows_sel = dev.rows[jnp.asarray(flat, jnp.int32)]
    else:
        # host fallback reads the compacted stream natively: only the
        # candidate leaves are padded, never the full [n_leaves, B] matrix
        rows_sel = jnp.asarray(view.to_leaf_stream().gather_padded(flat, view.B))
    with _trc.site("leaf_search"):
        found, _ = leaf_search(rows_sel, jnp.asarray(vs[qidx], jnp.int32),
                               q_block=q_block)
    np.logical_or.at(out, qidx, np.asarray(found))
    return out


__all__ = ["edge_search_view", "leaf_search", "leaf_search_ref"]
