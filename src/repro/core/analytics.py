"""Graph analytics over snapshot views (paper §7 workloads, GAPBS-style).

PR / BFS / SSSP / WCC run as jitted JAX programs over COO edge arrays
materialized from a :class:`~repro.core.snapshot.SnapshotView` — compiled
code contains zero version logic (the paper's decoupling).  Each program
drops edges whose ids lie outside ``[0, n)``: its gathers fill them with a
value neutral to the reduction, and its segment reductions discard them.
So a capacity-form device COO (``SnapshotView.to_coo_device``), whose tail
is SENTINEL, gives the same result as the exact COO, bitwise, with shapes
that hold across commits.  TC implements the
paper's hybrid set-intersection rule (merge when |N(v)|/|N(u)| < 10, probe
otherwise, §6.5) on the host, with a device path through the Pallas
``intersect`` kernel for leaf-block views.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import device_cache
from ..obs.trace import TRACER as _trc


# ---------------------------------------------------------------------------
# PageRank (push-style over COO; 10 iterations per GAPBS convention)
# ---------------------------------------------------------------------------
def _pr_step(agg: jnp.ndarray, dangling: jnp.ndarray, n: int, damping: float) -> jnp.ndarray:
    """The PageRank update expression, shared verbatim with the shard-plane
    collective kernel (:mod:`repro.core.shard_plane`).

    XLA's rounding of an elementwise expression can differ between two
    programs when the expression structure differs (FMA contraction,
    constant folding vs runtime evaluation): here ``damping`` is a traced
    f32 scalar in :func:`pagerank_coo` but a Python constant in the plane
    kernel, so the base term is built from the same f32 *ops* in both —
    XLA constant-folds them with identical IEEE semantics.  Routing both
    programs through this exact function is what makes the sharded
    PageRank bitwise-equal to this oracle.
    """
    d = jnp.float32(damping)
    base = (jnp.float32(1.0) - d) / n
    return base + d * (agg + dangling / n)


@partial(jax.jit, static_argnames=("n", "iters", "damping"))
def pagerank_coo(
    src: jnp.ndarray, dst: jnp.ndarray, n: int, iters: int = 10, damping: float = 0.85
) -> jnp.ndarray:
    # damping is static so the update constants are *folded* exactly as in
    # the shard-plane kernel (where damping is a closure constant) — a
    # traced scalar here would round the shared _pr_step expression
    # differently and break the cross-program bitwise contract.  An edge
    # out of [0, n) (a capacity tail) adds no degree, gathers 0 and is
    # dropped by the contribution's segment_sum.
    deg = jax.ops.segment_sum(jnp.ones_like(src, jnp.float32), src, num_segments=n)
    inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1.0), 0.0)
    p = jnp.full((n,), 1.0 / n, jnp.float32)

    def body(p, _):
        contrib = (p * inv_deg).at[src].get(mode="fill", fill_value=0.0)
        agg = jax.ops.segment_sum(contrib, dst, num_segments=n)
        dangling = jnp.sum(jnp.where(deg == 0, p, 0.0))
        return _pr_step(agg, dangling, n, damping), None

    p, _ = jax.lax.scan(body, p, None, length=iters)
    return p


# ---------------------------------------------------------------------------
# BFS (level-synchronous, dense frontiers)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n",))
def bfs_coo(src: jnp.ndarray, dst: jnp.ndarray, n: int, root: jnp.ndarray) -> jnp.ndarray:
    level = jnp.full((n,), -1, jnp.int32).at[root].set(0)

    def cond(state):
        level, frontier, d = state
        return jnp.any(frontier)

    def body(state):
        level, frontier, d = state
        reach = frontier.at[src].get(mode="fill", fill_value=False)
        hit = jax.ops.segment_max(reach.astype(jnp.int32), dst, num_segments=n)
        new = (hit > 0) & (level < 0)
        level = jnp.where(new, d + 1, level)
        return level, new, d + 1

    frontier = jnp.zeros((n,), bool).at[root].set(True)
    level, _, _ = jax.lax.while_loop(cond, body, (level, frontier, jnp.int32(0)))
    return level


# ---------------------------------------------------------------------------
# SSSP (Bellman-Ford with early exit)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n",))
def sssp_coo(
    src: jnp.ndarray, dst: jnp.ndarray, w: jnp.ndarray, n: int, root: jnp.ndarray
) -> jnp.ndarray:
    inf = jnp.float32(jnp.inf)
    dist = jnp.full((n,), inf, jnp.float32).at[root].set(0.0)

    def cond(state):
        dist, changed, it = state
        return changed & (it < n)

    def body(state):
        dist, _, it = state
        via = dist.at[src].get(mode="fill", fill_value=float("inf")) + w
        cand = jax.ops.segment_min(via, dst, num_segments=n)
        new = jnp.minimum(dist, cand)
        return new, jnp.any(new < dist), it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist, jnp.bool_(True), jnp.int32(0)))
    return dist


# ---------------------------------------------------------------------------
# WCC (label propagation; pass symmetrized edges for directed graphs)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n",))
def wcc_coo(src: jnp.ndarray, dst: jnp.ndarray, n: int) -> jnp.ndarray:
    labels = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        labels, changed = state
        return changed

    def body(state):
        labels, _ = state
        cand = jax.ops.segment_min(
            labels.at[src].get(mode="fill", fill_value=n), dst, num_segments=n
        )
        new = jnp.minimum(labels, cand)
        # pointer-jump (path halving) accelerates convergence
        new = new[new]
        return new, jnp.any(new != labels)

    labels, _ = jax.lax.while_loop(cond, body, (labels, jnp.bool_(True)))
    return labels


# ---------------------------------------------------------------------------
# View-level entry points — route jitted analytics through the memoized
# snapshot materializations (repeat queries on an unchanged view, or after a
# small write, reuse the cached per-subgraph arrays instead of rebuilding).
# By default they take the *device* COO (`view.to_coo_device()`): the edge
# arrays stay resident on the accelerator, so a warm repeat performs zero
# host->device transfers, and its capacity form lets a fresh view reuse the
# programs compiled for an older one.  Pass ``device=False`` (or set
# ``REPRO_DISABLE_DEVICE_CACHE``) for the host-array path.
#
# When the view's store has a shard plane attached
# (``RapidStore.attach_shard_plane``), the entry points route through the
# plane's ``shard_map`` collectives over mesh-pinned per-subgraph tiles
# instead (``REPRO_DISABLE_SHARD_PLANE`` or ``device=False`` opt out) —
# see :mod:`repro.core.shard_plane` for the parity contract.
# ---------------------------------------------------------------------------
def _view_coo(view, device: Optional[bool]):
    if device is None:
        device = device_cache.enabled()
    return view.to_coo_device() if device else view.to_coo()


def _plane(view, device: Optional[bool]):
    from . import shard_plane

    return shard_plane.active_plane(view, device)


def pagerank_view(
    view, iters: int = 10, damping: float = 0.85, device: Optional[bool] = None
) -> jnp.ndarray:
    with _trc.site("pagerank"):
        plane = _plane(view, device)
        if plane is not None:
            return plane.pagerank(view, iters=iters, damping=damping)
        src, dst = _view_coo(view, device)
        return pagerank_coo(src, dst, view.n_vertices, iters=iters, damping=damping)


def bfs_view(view, root: int, device: Optional[bool] = None) -> jnp.ndarray:
    plane = _plane(view, device)
    if plane is not None:
        return plane.bfs(view, root)
    src, dst = _view_coo(view, device)
    return bfs_coo(src, dst, view.n_vertices, root)


def sssp_view(view, w: np.ndarray, root: int, device: Optional[bool] = None) -> jnp.ndarray:
    plane = _plane(view, device)
    if plane is not None:
        return plane.sssp(view, w, root)
    src, dst = _view_coo(view, device)
    w = np.asarray(w, np.float32)
    # a capacity-form COO: weights for its tail, whose edges are dropped
    w = np.pad(w, (0, int(src.shape[0]) - view.n_edges))
    return sssp_coo(src, dst, jnp.asarray(w), view.n_vertices, root)


def wcc_view(view, device: Optional[bool] = None) -> jnp.ndarray:
    """WCC over a directed view: symmetrizes the cached COO (on device when
    the device cache is active — the concat never round-trips to host and
    keeps the capacity form's stable shapes; under a shard plane each shard
    symmetrizes its local edges in-kernel)."""
    plane = _plane(view, device)
    if plane is not None:
        return plane.wcc(view)
    src, dst = _view_coo(view, device)
    if isinstance(src, np.ndarray):
        return wcc_coo(
            np.concatenate([src, dst.astype(np.int64)]),
            np.concatenate([dst, src.astype(np.int32)]),
            view.n_vertices,
        )
    return wcc_coo(
        jnp.concatenate([src, dst]),
        jnp.concatenate([dst, src]),
        view.n_vertices,
    )


def triangle_count_view(view, device: Optional[bool] = None) -> int:
    """Triangle count over a snapshot view (store an undirected simple graph
    for exact counts).

    By default routes through the Pallas ``intersect_tiles_view`` entry point
    on the view's device-resident leaf tiles (paper §6.5's hybrid
    merge/probe rule applied as operand orientation); pass ``device=False``
    or set ``REPRO_DISABLE_DEVICE_CACHE`` for the host CSR loop.
    """
    if device is None:
        device = device_cache.enabled()
    if not device:
        return triangle_count_fast(view.to_csr())
    return _triangle_count_device(view)


def _triangle_count_device(view, batch: int = 8192) -> int:
    """Device TC: one Pallas intersect per (leaf-tile, leaf-tile) pair.

    Enumerate each undirected edge once as (u, v), u < v, and intersect the
    *full* neighbor tile sets of u and v on device: every common neighbor w
    closes the triangle {u, v, w}, and each triangle is discovered exactly
    once per edge — three times total — so the pair-count sum is 3T.  Tiles
    are the delta-plane assembled leaf blocks, so a repeat count after a
    small write re-uses every clean subgraph's device rows.

    The paper's hybrid rule (merge when the degree ratio < 10, probe
    otherwise) picks the operand *orientation*: probing keeps the smaller
    tile resident as `a` (see kernels.intersect.ops.intersect_count_hybrid).
    Assumes a simple graph (no self-loops), like the host oracle.
    """
    from repro.kernels.intersect import sum_intersect_tiles_view

    from . import view_assembler

    src, order = view_assembler.block_src_index(view)
    # the host side only needs per-leaf lengths: read the compacted stream's
    # sidecar natively — no padded [n, B] host materialization
    lens = np.asarray(view.to_leaf_stream().leaf_lens, np.int64)
    s_sorted = src[order]

    csr = view.to_csr()
    n = csr.n_vertices
    deg = np.diff(csr.offsets)
    eu = np.repeat(np.arange(n, dtype=np.int64), deg)
    ev = csr.indices.astype(np.int64)
    fwd = ev > eu  # orient each undirected edge low -> high, once
    eu, ev = eu[fwd], ev[fwd]
    if len(eu) == 0:
        return 0

    # per-edge tile spans via the src-sorted block index
    lo_u = np.searchsorted(s_sorted, eu, "left")
    hi_u = np.searchsorted(s_sorted, eu, "right")
    lo_v = np.searchsorted(s_sorted, ev, "left")
    hi_v = np.searchsorted(s_sorted, ev, "right")
    ku, kv = hi_u - lo_u, hi_v - lo_v
    pairs_per_edge = ku * kv
    total_pairs = int(pairs_per_edge.sum())
    if total_pairs == 0:
        return 0
    # all (tile of u) x (tile of v) pairs, vectorized
    e_idx = np.repeat(np.arange(len(eu)), pairs_per_edge)
    rank = np.arange(total_pairs, dtype=np.int64) - np.repeat(
        np.cumsum(pairs_per_edge) - pairs_per_edge, pairs_per_edge
    )
    ia = order[lo_u[e_idx] + rank // kv[e_idx]]
    ib = order[lo_v[e_idx] + rank % kv[e_idx]]
    # hybrid orientation: when the size ratio selects the probe strategy,
    # probe with the smaller tile as operand `a`
    la, lb = lens[ia], lens[ib]
    big, small = np.maximum(la, lb), np.maximum(np.minimum(la, lb), 1)
    swap = (big >= HYBRID_RATIO * small) & (la > lb)
    ia2 = np.where(swap, ib, ia)
    ib2 = np.where(swap, ia, ib)
    return sum_intersect_tiles_view(view, ia2, ib2, batch=batch) // 3


# ---------------------------------------------------------------------------
# Triangle counting — the paper's hybrid merge/probe intersection (§6.5)
# ---------------------------------------------------------------------------
HYBRID_RATIO = 10.0


def _intersect_count(a: np.ndarray, b: np.ndarray) -> int:
    """Count |a ∩ b| for sorted arrays with the paper's strategy rule."""
    d1, d2 = len(a), len(b)
    if d1 == 0 or d2 == 0:
        return 0
    if d1 > d2:
        a, b, d1, d2 = b, a, d2, d1
    if d2 / d1 < HYBRID_RATIO:  # merge-based
        return int(len(np.intersect1d(a, b, assume_unique=True)))
    # probe: binary-search each element of the smaller set in the larger
    pos = np.searchsorted(b, a)
    inb = pos < d2
    return int(np.count_nonzero(b[pos[inb]] == a[inb]))


def triangle_count(csr) -> int:
    """TC on an undirected CSR view: sum over edges (u,v), u<v of
    |N+(u) ∩ N+(v)| where N+ keeps only higher-id neighbors."""
    offsets, indices = np.asarray(csr.offsets), np.asarray(csr.indices)
    n = len(offsets) - 1
    # orient edges low->high to count each triangle once
    plus = []
    for u in range(n):
        nbr = indices[offsets[u] : offsets[u + 1]]
        plus.append(nbr[nbr > u])
    total = 0
    for u in range(n):
        for v in plus[u]:
            total += _intersect_count(plus[u], plus[int(v)])
    return total


def triangle_count_fast(csr) -> int:
    """Vectorized host TC used by benchmarks (same hybrid rule, batched)."""
    offsets, indices = np.asarray(csr.offsets), np.asarray(csr.indices)
    n = len(offsets) - 1
    deg = np.diff(offsets)
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    mask = indices > src  # orient
    e_src = src[mask]
    e_dst = indices[mask].astype(np.int64)
    total = 0
    # group by src for locality; probe each (u,v) pair's N+(v) against N+(u)
    for u in np.unique(e_src):
        nu = indices[offsets[u] : offsets[u + 1]]
        nu = nu[nu > u]
        if len(nu) == 0:
            continue
        for v in nu:
            nv = indices[offsets[v] : offsets[v + 1]]
            nv = nv[nv > v]
            total += _intersect_count(nu, nv)
    return total
