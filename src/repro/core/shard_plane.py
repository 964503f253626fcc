"""Sharded tile plane: mesh-distributed snapshot views with collective
analytics.

RapidStore's decoupling keeps version data out of graph data so concurrent
readers scale with cores; the same decoupling scales with *devices*.  Each
subgraph's leaf-block/COO tiles are independent immutable units, so placing
them across a 1-D JAX mesh turns view assembly into a set of per-device
splices and analytics into ``shard_map`` collectives over pinned tiles —
no host re-shard per call, no cross-device traffic on assembly.

Placement policy
----------------

A policy maps per-subgraph weights (edge counts at attach time) to a device
index per subgraph.  Built-ins:

- ``"modulo"`` (default): ``sid % n_devices`` — matches the paper-repro
  convention in :mod:`repro.core.distributed` and keeps placement trivially
  stable as subgraphs are appended.
- ``"degree_balanced"``: greedy bin packing — subgraphs sorted by weight,
  heaviest first, each assigned to the least-loaded device.  Evens out
  skewed graphs where modulo would land several hubs on one device.

Custom callables ``(weights, n_shards) -> assignment`` are accepted.

Placement is **versioned**, not an attach-time constant.  The attach-time
policy result seeds *epoch 0*; each migration committed by the rebalancer
(:mod:`repro.core.reshard`) appends a new epoch ``(commit_ts, placement)``
with the migrated subgraphs re-assigned.  A view resolves the placement of
the newest epoch at or below its own timestamp (:meth:`ShardPlane.
placement_at`), so every view at ``ts >= epoch`` sees the new placement and
every older view keeps resolving the old one — the exact MVCC rule the
version chains apply to graph data, applied to placement.  Within one
epoch, placement is still append-only (appended subgraphs get the policy's
choice for the extended id, identically across all epochs), so a
predecessor bundle's clean shards stay reusable for same-epoch successors;
across an epoch boundary, only the shards a migration or commit actually
touched are rebuilt and every other shard's arrays are still reused by
object identity.  Epochs are recorded in :class:`~repro.core.version_chain.
CommitLineage` (``record_placement``) and WAL-logged as no-write commits,
so recovery restores the same placement history.

Residency lifecycle
-------------------

A full build concatenates each shard's segments on the host and uploads
them to the shard's device in one transfer per column.  Splices and
migrations work per subgraph: per-(snapshot, device) tiles live in
:func:`repro.core.device_cache.shard_coo_tiles` / ``shard_leaf_tiles``,
uploaded once per snapshot version to the device the placement chose,
generation-stamped against recycled
:class:`~repro.core.leaf_pool.LeafPool` rows (the plane re-verifies the
stamp after every fetch and refuses to splice a stale tile), and dropped by
``SubgraphSnapshot.release()`` when writer-driven GC reclaims the version.
Leaf tiles cross the bus *compacted*: only the snapshot's packed stream
(values + lens/keys sidecars) is transferred, and the fixed-B SENTINEL
padding the collectives' Pallas kernels expect is synthesized on the shard
device after the upload — so the per-shard byte counters count live bytes.
Per-shard upload/byte counters in :class:`ShardPlaneStats` make the
transfer contract observable: after a commit dirtying subgraphs resident on
one shard, every other shard's upload counter stays flat (counter-asserted
in ``tests/test_shard_plane.py``).

Splice contract
---------------

Each view's :class:`~repro.core.view_assembler.ViewAssembly` carries a
:class:`ShardedViewAssembly`: per-device concatenated arrays padded to a
power-of-two capacity plus per-subgraph segment offsets.  A successor view
resolves its dirty set through :class:`~repro.core.version_chain.
CommitLineage` (the same ``_plan`` the host/device delta planes use) and

- reuses the predecessor bundle wholesale when the dirty set is empty;
- reuses every *clean shard's* arrays by object identity;
- on a dirty shard, uploads only the dirty subgraphs' tiles to that device
  and splices them in — ``jax.lax.dynamic_update_slice`` when every dirty
  segment keeps its size (padding and ``valid`` mask carry over), an
  O(dirty)-run concat + re-pad otherwise.

Capacities are powers of two, so small writes never resize; when a shard
does outgrow its capacity, the other shards re-pad device-locally (no
host->device transfer).  Every fallback (no predecessor, trimmed lineage,
dirty fraction above the splice threshold, ``REPRO_DISABLE_DELTA_SPLICE``)
routes to a full per-shard rebuild: one bulk upload per shard and column.

Collectives
-----------

``pagerank`` / ``bfs`` / ``sssp`` / ``wcc`` / ``spmm`` run under
``shard_map`` over the global arrays assembled zero-copy from the per-shard
buffers (``jax.make_array_from_single_device_arrays``).  The COO kernels
are :mod:`repro.core.distributed`'s builders (``make_pagerank(pull=...)``,
``make_bfs``, ``make_sssp``, ``make_wcc``) — one copy of each vertex-cut
local-reduce + collective kernel, here reading pinned shard tiles instead
of host arrays re-sharded per call.  The merges are arranged for *bitwise*
parity with the single-device ``*_view`` oracles:

- min/max merges (BFS ``pmax``, SSSP/WCC ``pmin``) are order-independent,
  hence exact on any store;
- SpMM aggregates by *source* vertex: the store's partitioning gives every
  source vertex to exactly one shard, so the ``psum`` adds exact zeros;
- PageRank uses the *pull* form over each shard's own out-edges (gather at
  dst, scatter by src): on a symmetrized store (``symmetric=True``; the
  repo's convention for undirected analytics) this reproduces the oracle's
  per-vertex fold order exactly, again making ``psum`` an exact merge.  On
  a directed store pass ``symmetric=False`` (the default) to get the push
  form — numerically standard vertex-cut PageRank, equal to the oracle to
  rounding but not bitwise.  Both share the oracle's update expression
  (:func:`repro.core.analytics._pr_step`) so XLA makes identical
  FMA-contraction choices across the two programs.

``XLA_FLAGS=--xla_force_host_platform_device_count=N`` +
:func:`repro.launch.mesh.make_shard_mesh` make the whole path testable on
CPU; ``REPRO_DISABLE_SHARD_PLANE=1`` routes the ``*_view`` entry points
back to the single-device paths.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from .hooks import RESHARD_HOOKS
from .leaf_pool import SENTINEL


def enabled() -> bool:
    """Shard-plane routing switch (``REPRO_DISABLE_SHARD_PLANE`` opts out)."""
    return not os.environ.get("REPRO_DISABLE_SHARD_PLANE")


def active_plane(view, device=None):
    """The plane that should serve ``view``'s collective analytics, or None.

    ``device=False`` (the explicit host-path request of the ``*_view``
    entry points) bypasses the plane; ``device=None`` defers to the device
    cache switch, matching the existing routing convention.
    """
    plane = getattr(view, "_plane", None)
    if plane is None or device is False or not enabled():
        return None
    if device is None:
        from . import device_cache

        if not device_cache.enabled():
            return None
    return plane


# ---------------------------------------------------------------------------
# Placement policies
# ---------------------------------------------------------------------------
def modulo_placement(weights: np.ndarray, n_shards: int) -> np.ndarray:
    """``sid % n_shards`` — stable, oblivious to skew."""
    return np.arange(len(weights), dtype=np.int64) % n_shards


def degree_balanced_placement(weights: np.ndarray, n_shards: int) -> np.ndarray:
    """Greedy bin packing: heaviest subgraph first onto the lightest device.

    Classic LPT scheduling — load within 4/3 of optimal, good enough to keep
    a power-law graph's hub subgraphs off one device.  Deterministic: ties
    break toward the lowest device index, equal weights toward the lower
    subgraph id (stable argsort).
    """
    weights = np.asarray(weights, np.int64)
    order = np.argsort(-weights, kind="stable")
    loads = np.zeros(n_shards, np.int64)
    out = np.zeros(len(weights), np.int64)
    for sid in order:
        k = int(np.argmin(loads))
        out[sid] = k
        loads[k] += weights[sid]
    return out


_POLICIES: Dict[str, Callable] = {
    "modulo": modulo_placement,
    "degree_balanced": degree_balanced_placement,
}


# ---------------------------------------------------------------------------
# Stats — the observable per-shard transfer contract
# ---------------------------------------------------------------------------
@dataclass
class ShardPlaneStats:
    """Counters for one plane (lock-protected by the plane's lock).

    ``uploads[k]`` / ``bytes_uploaded[k]`` count host->device segment
    uploads to shard ``k`` during view assembly — the acceptance criterion
    "a write dirtying subgraphs on one shard uploads only to that shard" is
    asserted as every other shard's counter staying flat.  ``repads``
    counts device-local capacity re-pads (no host transfer involved).
    """

    n_shards: int = 1
    uploads: List[int] = field(default_factory=list)
    bytes_uploaded: List[int] = field(default_factory=list)
    assemblies: int = 0
    splices: int = 0
    full_builds: int = 0
    reuses: int = 0
    shard_reuses: int = 0
    repads: int = 0
    spliced_segments: int = 0
    operand_uploads: int = 0
    collective_calls: int = 0
    migration_rebuilds: int = 0

    def __post_init__(self) -> None:
        if not self.uploads:
            self.uploads = [0] * self.n_shards
        if not self.bytes_uploaded:
            self.bytes_uploaded = [0] * self.n_shards

    def reset(self) -> None:
        self.uploads = [0] * self.n_shards
        self.bytes_uploaded = [0] * self.n_shards
        self.assemblies = 0
        self.splices = 0
        self.full_builds = 0
        self.reuses = 0
        self.shard_reuses = 0
        self.repads = 0
        self.spliced_segments = 0
        self.operand_uploads = 0
        self.collective_calls = 0
        self.migration_rebuilds = 0


# ---------------------------------------------------------------------------
# Per-shard bundles
# ---------------------------------------------------------------------------
class ShardBundle:
    """One device's padded tile columns + per-subgraph segment offsets.

    ``cols`` are committed ``jax.Array``s stored in the *global component
    layout* ``[1, cap, ...]`` — exactly the per-device piece
    ``jax.make_array_from_single_device_arrays`` wants, so assembling the
    global arrays wraps these buffers without copying (a trailing
    ``reshape`` at assembly time would copy every column on every view).
    ``offsets[i]`` spans subgraph ``sids[i]``'s segment inside the live
    prefix ``[:, 0:n_live]``.  Padding uses SENTINEL ids (out of range for
    every vertex count, so segment reductions drop pad slots) and, for COO,
    an explicit ``valid`` mask.
    """

    __slots__ = ("device", "sids", "offsets", "n_live", "cap", "cols", "valid")

    def __init__(self, device, sids, offsets, n_live, cap, cols, valid=None):
        self.device = device
        self.sids = sids  # np int64, ascending
        self.offsets = offsets  # np int64 [len(sids)+1]
        self.n_live = int(n_live)
        self.cap = int(cap)
        self.cols = cols  # tuple of jax.Array, leading dim == cap
        self.valid = valid  # jax.Array bool [cap] (COO kinds only)

    def nbytes(self) -> int:
        total = sum(int(c.nbytes) for c in self.cols)
        if self.valid is not None:
            total += int(self.valid.nbytes)
        return total


class ShardedKind:
    """One materialization kind (COO or leaf blocks) across all shards."""

    __slots__ = ("cap", "shards", "seg_counts", "_global")

    def __init__(self, cap: int, shards: List[ShardBundle], seg_counts: np.ndarray):
        self.cap = int(cap)
        self.shards = shards
        # per-subgraph segment length, indexed by sid — the splice map and
        # the global-offset source for per-edge operands (SSSP weights)
        self.seg_counts = seg_counts
        self._global: Optional[tuple] = None

    def nbytes(self) -> int:
        return sum(s.nbytes() for s in self.shards)

    def global_arrays(self, mesh, axis: str) -> tuple:
        """Global jax.Arrays ([K, cap, ...]) wrapping the shard buffers.

        Zero-copy: the per-shard columns already have the ``[1, cap, ...]``
        component shape, so the global array is a view over the same
        device buffers — no transfer, no duplicate residency.
        """
        if self._global is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            K = len(self.shards)
            cols_out = []
            n_cols = len(self.shards[0].cols)
            for i in range(n_cols):
                parts = [s.cols[i] for s in self.shards]
                shape = (K,) + parts[0].shape[1:]
                spec = P(axis, *([None] * (len(shape) - 1)))
                cols_out.append(
                    jax.make_array_from_single_device_arrays(
                        shape, NamedSharding(mesh, spec), parts
                    )
                )
            if self.shards[0].valid is not None:
                parts = [s.valid for s in self.shards]
                cols_out.append(
                    jax.make_array_from_single_device_arrays(
                        (K, self.cap), NamedSharding(mesh, P(axis, None)), parts
                    )
                )
            self._global = tuple(cols_out)
        return self._global


class ShardedViewAssembly:
    """Mesh twin of :class:`~repro.core.view_assembler.ViewAssembly`.

    Held on ``ViewAssembly.sharded`` so it rides the store's existing
    retire / weak-predecessor lifecycle: the newest retired view's bundle
    is the splice source for its successor, and GC of superseded bundles
    frees the per-shard arrays (the per-snapshot tiles stay pinned in the
    device cache until their snapshot is released).
    """

    __slots__ = ("ts", "S", "placement", "coo", "blocks")

    def __init__(self, ts: int, S: int, placement: np.ndarray) -> None:
        self.ts = ts
        self.S = S
        self.placement = placement  # np int64 [S]
        self.coo: Optional[ShardedKind] = None
        self.blocks: Optional[ShardedKind] = None

    def device_bytes(self) -> int:
        total = 0
        for kind in (self.coo, self.blocks):
            if kind is not None:
                total += kind.nbytes()
        return total


def _round_cap(n_live: int, floor: int) -> int:
    """Power-of-two capacity >= max(floor, n_live): small writes never
    resize, so clean shards' padded arrays stay splice-compatible."""
    cap = int(floor)
    while cap < n_live:
        cap *= 2
    return cap


# ---------------------------------------------------------------------------
# The plane
# ---------------------------------------------------------------------------
class ShardPlane:
    """Mesh-resident tile subsystem for one :class:`~repro.core.store.
    RapidStore` (see the module docstring for the full contract).

    ``symmetric=True`` declares the store holds a symmetrized graph (every
    edge stored in both directions); PageRank then uses the pull form that
    is bitwise-equal to the single-device oracle.
    """

    _COO_FLOOR = 256  # min edge capacity per shard
    _BLK_FLOOR = 64  # min leaf-tile capacity per shard

    def __init__(
        self,
        store,
        mesh=None,
        n_devices: Optional[int] = None,
        policy: Union[str, Callable] = "modulo",
        symmetric: bool = False,
    ) -> None:
        from repro.launch.mesh import make_shard_mesh

        self.store = store
        self.mesh = mesh if mesh is not None else make_shard_mesh(n_devices)
        if len(self.mesh.axis_names) != 1:
            raise ValueError(
                f"shard plane needs a 1-D mesh, got axes {self.mesh.axis_names}"
            )
        self.axis = self.mesh.axis_names[0]
        self.devices = list(self.mesh.devices.flat)
        self.n_shards = len(self.devices)
        self.symmetric = bool(symmetric)
        self._policy_name = policy if isinstance(policy, str) else "custom"
        self._policy = _POLICIES[policy] if isinstance(policy, str) else policy
        self._lock = threading.Lock()
        self.stats = ShardPlaneStats(self.n_shards)
        self._fn_cache: Dict[tuple, Callable] = {}
        weights = np.array(
            [c.head.n_edges for c in store.chains], np.int64
        )
        base = np.asarray(self._policy(weights, self.n_shards), np.int64).copy()
        # versioned placement: ascending (epoch_ts, placement) pairs; epoch 0
        # is the attach-time policy result, each migration flip appends a new
        # pair.  Arrays are immutable once stored (extension and flips both
        # append fresh arrays), so slices handed to views stay valid forever.
        self._epochs: List[tuple] = [(0, base)]
        self._loads = np.bincount(
            base, weights=weights, minlength=self.n_shards
        ).astype(np.int64)
        # nominal weight charged per appended subgraph: without it the
        # least-loaded argmin below would keep answering the same shard and
        # every append would pile onto one device
        self._nominal = max(1, int(weights.mean()) if len(weights) else 1)
        self._registered: List[tuple] = []
        self._register_metrics()

    # -- telemetry -----------------------------------------------------------
    def _register_metrics(self) -> None:
        """Per-shard gauges on the owning store's registry.

        These are the rebalancer's primary signals (alongside the write
        pipeline's ``pipeline_queue_depth``): per-shard upload counters and
        the current-epoch edge load.  :meth:`close` unregisters every one —
        ``detach_shard_plane`` must leave the registry exactly as it found
        it (regression-pinned in ``tests/test_obs.py``).
        """
        reg = getattr(self.store, "registry", None)
        if reg is None:  # pragma: no cover - stores always carry a registry
            return
        for k in range(self.n_shards):
            labels = {"shard": str(k)}
            reg.gauge("shard_plane_uploads",
                      fn=lambda k=k: self.stats.uploads[k], **labels)
            reg.gauge("shard_plane_bytes_uploaded",
                      fn=lambda k=k: self.stats.bytes_uploaded[k], **labels)
            reg.gauge("shard_plane_load",
                      fn=lambda k=k: self.shard_load(k), **labels)
            self._registered += [
                ("shard_plane_uploads", labels),
                ("shard_plane_bytes_uploaded", labels),
                ("shard_plane_load", labels),
            ]
        reg.gauge("shard_plane_epoch", fn=lambda: self.current_epoch)
        self._registered.append(("shard_plane_epoch", {}))

    def close(self) -> None:
        """Unregister this plane's per-shard metrics (idempotent)."""
        reg = getattr(self.store, "registry", None)
        if reg is not None:
            for name, labels in self._registered:
                reg.unregister(name, **labels)
        self._registered = []

    def shard_load(self, k: int) -> int:
        """Edge weight resident on shard ``k`` under the current placement."""
        with self._lock:
            placement = self._epochs[-1][1]
        chains = self.store.chains
        lim = min(len(placement), len(chains))
        return int(sum(
            chains[sid].head.n_edges
            for sid in range(lim) if int(placement[sid]) == k
        ))

    # -- placement -----------------------------------------------------------
    @property
    def current_epoch(self) -> int:
        """Commit timestamp of the newest placement epoch (0 = attach)."""
        return self._epochs[-1][0]

    def _extend_locked(self, S: int) -> None:
        """Append-extend every epoch's placement to length ``S``.

        Appended subgraphs get the SAME assignment in every epoch — they
        did not exist when older epochs were committed, so there is nothing
        for those epochs to disagree about, and sharing the assignment
        keeps old-timestamp views (which can still see an appended
        subgraph's empty version-0 snapshot) consistent with new ones.
        """
        cur = self._epochs[-1][1]
        while len(cur) < S:
            sid = len(cur)
            if self._policy is modulo_placement:
                k = sid % self.n_shards
            else:
                k = int(np.argmin(self._loads))
                self._loads[k] += self._nominal
            self._epochs = [
                (ts, np.append(arr, k)) for ts, arr in self._epochs
            ]
            cur = self._epochs[-1][1]

    def placement_for(self, S: int) -> np.ndarray:
        """The *current* (newest-epoch) placement, append-extended to ``S``.

        Within an epoch, assignments never move (clean-shard reuse depends
        on it); appended subgraphs go to ``sid % K`` under modulo and to
        the least-loaded device otherwise.  Views resolve placement by
        their own timestamp via :meth:`placement_at`.
        """
        with self._lock:
            self._extend_locked(S)
            return self._epochs[-1][1][:S]

    def placement_at(self, ts: int, S: int) -> np.ndarray:
        """Placement of the newest epoch with ``epoch_ts <= ts``.

        The MVCC read rule for placement: a view pinned at ``ts`` resolves
        the epoch that was current when ``ts`` was published, so a
        migration flip at epoch E never changes what an older view sees.
        """
        with self._lock:
            self._extend_locked(S)
            lo, hi = 0, len(self._epochs) - 1
            while lo < hi:  # rightmost epoch with epoch_ts <= ts
                mid = (lo + hi + 1) // 2
                if self._epochs[mid][0] <= ts:
                    lo = mid
                else:
                    hi = mid - 1
            return self._epochs[lo][1][:S]

    def record_epoch(self, ts: int, moves: Dict[int, int]) -> None:
        """Append a placement epoch at commit timestamp ``ts``.

        Called by the migration runtime after its WAL record is durable and
        BEFORE ``ts`` publishes (record-before-publish, like lineage), and
        by ``attach_shard_plane`` replaying a recovered store's placement
        log.  Destination shard indices are folded ``% n_shards`` so a log
        recorded on a larger mesh re-attaches deterministically to a
        smaller one (restoration is exact when the mesh size matches).
        """
        with self._lock:
            prev_ts, prev = self._epochs[-1]
            if ts <= prev_ts:
                raise ValueError(
                    f"placement epoch {ts} not after newest epoch {prev_ts}"
                )
            if moves:
                self._extend_locked(max(int(s) for s in moves) + 1)
                prev = self._epochs[-1][1]
            nxt = prev.copy()
            for sid, k in moves.items():
                nxt[int(sid)] = int(k) % self.n_shards
            self._epochs.append((int(ts), nxt))
            weights = np.array(
                [c.head.n_edges for c in self.store.chains], np.int64
            )
            lim = min(len(weights), len(nxt))
            self._loads = np.bincount(
                nxt[:lim], weights=weights[:lim], minlength=self.n_shards
            ).astype(np.int64)

    def placement_epochs(self) -> List[tuple]:
        """Snapshot of the epoch history: ``[(epoch_ts, placement), ...]``."""
        with self._lock:
            return [(ts, arr.copy()) for ts, arr in self._epochs]

    # -- residency -----------------------------------------------------------
    def _fetch(self, snap, k: int, fetch_fn) -> tuple:
        """One subgraph's tiles on shard ``k``, upload-counted + stamped."""
        from . import device_cache

        tiles, nbytes = fetch_fn(snap, self.devices[k], wait=False)
        if not device_cache.tiles_fresh(snap):
            raise RuntimeError(
                f"subgraph {snap.sid} shard tiles went stale during assembly "
                "(pool-row generation advanced under a live snapshot)"
            )
        if nbytes:
            with self._lock:
                self.stats.uploads[k] += 1
                self.stats.bytes_uploaded[k] += nbytes
        return tiles

    # -- assembly ------------------------------------------------------------
    def _kind_params(self, kind: str, view):
        from . import device_cache

        if kind == "coo":
            return (
                device_cache.shard_coo_tiles,
                self._COO_FLOOR,
                (SENTINEL, SENTINEL),
                True,
            )
        return (
            device_cache.shard_leaf_tiles,
            self._BLK_FLOOR,
            (SENTINEL, SENTINEL, np.int32(0)),
            False,
        )

    def _finalize_cols(self, live_cols, cap: int, pad_vals, with_valid: bool, n_live: int, device):
        """Pad 1-D-leading live columns to ``cap`` and lift them into the
        ``[1, cap, ...]`` global component layout (one device-local reshape
        per rebuilt shard — clean shards and global assembly never copy).

        Every finished column is committed to ``device``: zero-element
        intermediates (an all-deleted subgraph's live columns) lose their
        committed device under jax — any op with a 0-sized output lands on
        the default device — and a shard bundle whose buffers sit on the
        wrong device breaks ``make_array_from_single_device_arrays``.  The
        ``device_put`` is a no-op for the already-resident common case.
        """
        import jax
        import jax.numpy as jnp

        cols = []
        for col, pv in zip(live_cols, pad_vals):
            pad = cap - int(col.shape[0])
            if pad:
                widths = ((0, pad),) + ((0, 0),) * (col.ndim - 1)
                col = jnp.pad(col, widths, constant_values=pv)
            cols.append(jax.device_put(col[None], device))
        valid = None
        if with_valid:
            valid = jax.device_put(
                (jnp.cumsum(jnp.ones_like(cols[0], jnp.int32), axis=1) - 1) < n_live,
                device,
            )
        return tuple(cols), valid

    def _empty_cols(self, k: int, kind: str, B: int):
        """Zero-length committed columns on shard ``k`` (0-byte transfer)."""
        import jax

        if kind == "coo":
            hosts = [np.zeros(0, np.int32)] * 2
        else:
            hosts = [np.zeros(0, np.int32), np.zeros((0, B), np.int32), np.zeros(0, np.int32)]
        return tuple(jax.device_put(h, self.devices[k]) for h in hosts)

    def _build_full(self, view, placement: np.ndarray, kind: str) -> ShardedKind:
        """Every shard from scratch: each shard's segments are concatenated
        on the host and cross the bus in one upload per column (leaf tiles
        re-padded on the shard device by one program).  Per-subgraph uploads
        would compile one program per distinct segment shape and device."""
        from . import device_cache

        _fetch_fn, floor, pad_vals, with_valid = self._kind_params(kind, view)
        S = len(view.snaps)
        # raises on released snapshots; the arrays are copies of pool rows
        if kind == "coo":
            host = [s.to_coo_global() for s in view.snaps]
        else:
            host = [
                (t[0], t[2], t[3])  # (data, lens, keys) of the packed stream
                for t in (s.to_leaf_stream_global() for s in view.snaps)
            ]
        seg_counts = np.array(
            [len(h[0]) if kind == "coo" else len(h[1]) for h in host], np.int64
        )
        sids_by_shard = [
            np.nonzero(placement[:S] == k)[0].astype(np.int64)
            for k in range(self.n_shards)
        ]
        lives = [int(seg_counts[sids].sum()) for sids in sids_by_shard]
        cap = _round_cap(max(lives) if lives else 0, floor)
        shards = []
        for k, sids in enumerate(sids_by_shard):
            if len(sids):
                cols_host = tuple(
                    np.concatenate([host[sid][i] for sid in sids])
                    for i in range(len(host[0]))
                )
                up = device_cache._device_put(
                    cols_host, wait=False, device=self.devices[k]
                )
                with self._lock:
                    self.stats.uploads[k] += 1
                    self.stats.bytes_uploaded[k] += int(
                        sum(int(c.nbytes) for c in up)
                    )
                if kind == "coo":
                    live_cols = up
                else:
                    data, lens, keys = up
                    rows = device_cache._pad_tiles_on_device(data, lens, view.B)
                    live_cols = (keys, rows, lens)
            else:
                live_cols = self._empty_cols(k, kind, view.B)
            offsets = np.zeros(len(sids) + 1, np.int64)
            np.cumsum(seg_counts[sids], out=offsets[1:])
            cols, valid = self._finalize_cols(
                live_cols, cap, pad_vals, with_valid, lives[k], self.devices[k]
            )
            shards.append(
                ShardBundle(
                    self.devices[k], sids, offsets, lives[k], cap, cols, valid,
                )
            )
        with self._lock:
            self.stats.full_builds += 1
        return ShardedKind(cap, shards, seg_counts)

    def _splice_kind(
        self,
        view,
        placement: np.ndarray,
        pred_kind: ShardedKind,
        pred_S: int,
        dirty: Sequence[int],
        kind: str,
    ) -> ShardedKind:
        import jax
        import jax.numpy as jnp

        fetch_fn, floor, pad_vals, with_valid = self._kind_params(kind, view)
        S = len(view.snaps)
        seg_counts = np.zeros(S, np.int64)
        seg_counts[:pred_S] = pred_kind.seg_counts[:pred_S]
        # fetch fresh segments, grouped by shard
        fresh: Dict[int, Dict[int, tuple]] = {}
        for sid in dirty:
            k = int(placement[sid])
            tiles = self._fetch(view.snaps[sid], k, fetch_fn)
            fresh.setdefault(k, {})[sid] = tiles
            seg_counts[sid] = int(tiles[0].shape[0])
        # sid -> index maps, built only for shards with fresh segments:
        # clean shards never consult them, and building all K would cost
        # O(S) host work per splice regardless of the dirty count
        pred_pos_all = {
            k: {int(s): i for i, s in enumerate(pred_kind.shards[k].sids)}
            for k in fresh
        }
        lives = []
        for k in range(self.n_shards):
            pred_shard = pred_kind.shards[k]
            live = pred_shard.n_live
            for sid, tiles in fresh.get(k, {}).items():
                i = pred_pos_all[k].get(sid)
                old = (
                    int(pred_shard.offsets[i + 1] - pred_shard.offsets[i])
                    if i is not None
                    else 0
                )
                live += int(tiles[0].shape[0]) - old
            lives.append(live)
        cap = max(pred_kind.cap, _round_cap(max(lives), floor))
        shards: List[ShardBundle] = []
        n_spliced = 0
        for k in range(self.n_shards):
            pred_shard = pred_kind.shards[k]
            fresh_k = fresh.get(k, {})
            if not fresh_k:
                if cap == pred_kind.cap:
                    shards.append(pred_shard)  # wholesale reuse, zero work
                    with self._lock:
                        self.stats.shard_reuses += 1
                else:
                    # capacity grew on another shard: re-pad device-locally
                    cols, valid = self._finalize_cols(
                        tuple(c[0, : pred_shard.n_live] for c in pred_shard.cols),
                        cap, pad_vals, with_valid, pred_shard.n_live,
                        pred_shard.device,
                    )
                    shards.append(
                        ShardBundle(
                            pred_shard.device, pred_shard.sids, pred_shard.offsets,
                            pred_shard.n_live, cap, cols, valid,
                        )
                    )
                    with self._lock:
                        self.stats.repads += 1
                continue
            n_spliced += len(fresh_k)
            # this shard's sids after the splice (pred set + appended tail)
            sids_k = np.asarray(
                sorted(set(pred_shard.sids.tolist()) | set(fresh_k)), np.int64
            )
            pred_pos = pred_pos_all[k]
            counts = []
            for sid in sids_k:
                if int(sid) in fresh_k:
                    counts.append(int(fresh_k[int(sid)][0].shape[0]))
                else:
                    i = pred_pos[int(sid)]
                    counts.append(
                        int(pred_shard.offsets[i + 1] - pred_shard.offsets[i])
                    )
            offsets = np.zeros(len(counts) + 1, np.int64)
            np.cumsum(counts, out=offsets[1:])
            n_live = int(offsets[-1])
            same_layout = (
                cap == pred_kind.cap
                and len(sids_k) == len(pred_shard.sids)
                and all(int(s) in pred_pos for s in sids_k)
                and all(
                    int(fresh_k[int(sid)][0].shape[0])
                    == int(
                        pred_shard.offsets[pred_pos[int(sid)] + 1]
                        - pred_shard.offsets[pred_pos[int(sid)]]
                    )
                    for sid in fresh_k
                )
            )
            if same_layout:
                # in-place patch: pad region and valid mask carry over
                cols = []
                for i, col in enumerate(pred_shard.cols):
                    base = col  # [1, cap, ...] global component layout
                    for sid in sorted(fresh_k):
                        seg = fresh_k[sid][i]
                        if seg.shape[0] == 0:
                            continue
                        lo = int(pred_shard.offsets[pred_pos[sid]])
                        start = (0, lo) + (0,) * (seg.ndim - 1)
                        base = jax.lax.dynamic_update_slice(base, seg[None], start)
                    cols.append(base)
                shards.append(
                    ShardBundle(
                        pred_shard.device, sids_k, offsets, n_live, cap,
                        tuple(cols), pred_shard.valid,
                    )
                )
            else:
                # O(dirty)-run rebuild: fresh segments interleave with runs
                # of the pred live prefix; consecutive clean sids collapse
                # into one contiguous pred slice (their pred positions are
                # adjacent, so their offsets span one interval)
                parts: List[list] = [[] for _ in pred_shard.cols]
                i = 0
                while i < len(sids_k):
                    sid = int(sids_k[i])
                    if sid in fresh_k:
                        seg = fresh_k[sid]
                        if seg[0].shape[0]:
                            for c in range(len(parts)):
                                parts[c].append(seg[c])
                        i += 1
                        continue
                    j = i
                    while (
                        j + 1 < len(sids_k)
                        and int(sids_k[j + 1]) not in fresh_k
                        and pred_pos[int(sids_k[j + 1])]
                        == pred_pos[int(sids_k[j])] + 1
                    ):
                        j += 1
                    lo = int(pred_shard.offsets[pred_pos[sid]])
                    hi = int(pred_shard.offsets[pred_pos[int(sids_k[j])] + 1])
                    if hi > lo:
                        for c, col in enumerate(pred_shard.cols):
                            parts[c].append(col[0, lo:hi])
                    i = j + 1
                if parts[0]:
                    live_cols = tuple(
                        jnp.concatenate(p) if len(p) > 1 else p[0] for p in parts
                    )
                else:
                    live_cols = self._empty_cols(k, kind, view.B)
                cols, valid = self._finalize_cols(
                    live_cols, cap, pad_vals, with_valid, n_live,
                    pred_shard.device,
                )
                shards.append(
                    ShardBundle(
                        pred_shard.device, sids_k, offsets, n_live, cap, cols, valid
                    )
                )
        with self._lock:
            self.stats.splices += 1
            self.stats.spliced_segments += n_spliced
        return ShardedKind(cap, shards, seg_counts)

    def _rebuild_moved(
        self,
        view,
        placement: np.ndarray,
        pred_kind: ShardedKind,
        pred_placement: np.ndarray,
        pred_S: int,
        dirty: Sequence[int],
        kind: str,
    ) -> ShardedKind:
        """Cross-epoch splice: predecessor from an older placement epoch.

        Only the shards a migration or commit actually touched rebuild —
        the source and destination shard of every moved subgraph, plus the
        shard of every lineage-dirty or appended subgraph; every other
        shard's arrays are reused by object identity (counter-asserted in
        ``tests/test_property_reshard.py``).  Touched shards refetch all of
        their subgraphs' tiles, which is a per-(snapshot, device) cache hit
        for every clean already-resident subgraph and an upload only for
        the moved/dirty ones (the migration runtime pre-stages the moved
        tiles, so even those are usually hits).
        """
        import jax.numpy as jnp

        fetch_fn, floor, pad_vals, with_valid = self._kind_params(kind, view)
        S = len(view.snaps)
        lim = min(int(pred_S), S)
        moved = [
            sid for sid in range(lim)
            if int(pred_placement[sid]) != int(placement[sid])
        ]
        touched = {int(placement[s]) for s in list(dirty) + moved}
        touched |= {int(pred_placement[s]) for s in moved}
        seg_counts = np.zeros(S, np.int64)
        seg_counts[:lim] = pred_kind.seg_counts[:lim]
        fetched: Dict[int, Dict[int, tuple]] = {k: {} for k in touched}
        for sid in range(S):
            k = int(placement[sid])
            if k in fetched:
                tiles = self._fetch(view.snaps[sid], k, fetch_fn)
                fetched[k][sid] = tiles
                seg_counts[sid] = int(tiles[0].shape[0])
        lives_touched = [
            sum(int(t[0].shape[0]) for t in fk.values())
            for fk in fetched.values()
        ]
        cap = max(
            pred_kind.cap,
            _round_cap(max(lives_touched) if lives_touched else 0, floor),
        )
        shards: List[ShardBundle] = []
        for k in range(self.n_shards):
            pred_shard = pred_kind.shards[k]
            if k not in touched:
                # no subgraph moved in or out and none dirty: this shard's
                # sid set and contents are unchanged across the epoch flip
                if cap == pred_kind.cap:
                    shards.append(pred_shard)
                    with self._lock:
                        self.stats.shard_reuses += 1
                else:
                    cols, valid = self._finalize_cols(
                        tuple(c[0, : pred_shard.n_live] for c in pred_shard.cols),
                        cap, pad_vals, with_valid, pred_shard.n_live,
                        pred_shard.device,
                    )
                    shards.append(
                        ShardBundle(
                            pred_shard.device, pred_shard.sids,
                            pred_shard.offsets, pred_shard.n_live, cap, cols,
                            valid,
                        )
                    )
                    with self._lock:
                        self.stats.repads += 1
                continue
            fk = fetched[k]
            sids_k = np.asarray(sorted(fk), np.int64)
            counts = [int(fk[int(s)][0].shape[0]) for s in sids_k]
            offsets = np.zeros(len(counts) + 1, np.int64)
            np.cumsum(counts, out=offsets[1:])
            n_live = int(offsets[-1])
            tiles_k = [fk[int(s)] for s in sids_k]
            if tiles_k:
                n_cols = len(tiles_k[0])
                live_cols = tuple(
                    jnp.concatenate([t[i] for t in tiles_k])
                    if len(tiles_k) > 1 else tiles_k[0][i]
                    for i in range(n_cols)
                )
            else:
                live_cols = self._empty_cols(k, kind, view.B)
            cols, valid = self._finalize_cols(
                live_cols, cap, pad_vals, with_valid, n_live, self.devices[k]
            )
            shards.append(
                ShardBundle(
                    self.devices[k], sids_k, offsets, n_live, cap, cols, valid
                )
            )
        with self._lock:
            self.stats.migration_rebuilds += 1
        return ShardedKind(cap, shards, seg_counts)

    def _sharded_kind(self, view, kind: str) -> ShardedKind:
        from . import view_assembler

        a = view_assembler._bundle(view)
        sh = a.sharded
        S = len(view.snaps)
        # versioned placement: resolve the epoch current at THIS view's
        # timestamp, so a migration flip never changes an older view
        placement = self.placement_at(view.ts, S)
        RESHARD_HOOKS.fire("hook_before_assembly", ts=view.ts, kind=kind)
        if sh is None:
            sh = ShardedViewAssembly(view.ts, S, np.array(placement))
            a.sharded = sh
        cur = getattr(sh, kind)
        if cur is not None:
            return cur
        with self._lock:
            self.stats.assemblies += 1
        plan = view_assembler._plan(view)
        pred_kind = None
        pred_moved = None  # predecessor from an older placement epoch
        pred_S = 0
        if plan is not None:
            pred_b, dirty = plan
            psh = pred_b.sharded
            cand = getattr(psh, kind, None) if psh is not None else None
            if (
                cand is not None
                and psh.placement is not None
                and len(psh.placement) <= S
                # the bundle must have been built against THIS plane's mesh:
                # a re-attached plane with a different shard count or device
                # order cannot splice (or reuse) the old per-shard arrays
                and len(cand.shards) == self.n_shards
                and all(
                    b.device == d for b, d in zip(cand.shards, self.devices)
                )
            ):
                if np.array_equal(
                    psh.placement, placement[: len(psh.placement)]
                ):
                    pred_kind = cand
                    pred_S = psh.S
                else:
                    # the predecessor was assembled under a different
                    # placement epoch: its untouched shards are still
                    # reusable, only migrated/dirty shards rebuild
                    pred_moved = (cand, psh.placement, psh.S)
        if pred_kind is not None:
            if not dirty and pred_S == S:
                setattr(sh, kind, pred_kind)  # wholesale bundle reuse
                with self._lock:
                    self.stats.reuses += 1
                return pred_kind
            built = self._splice_kind(view, placement, pred_kind, pred_S, dirty, kind)
        elif pred_moved is not None:
            built = self._rebuild_moved(
                view, placement, pred_moved[0], pred_moved[1], pred_moved[2],
                dirty, kind,
            )
        else:
            built = self._build_full(view, placement, kind)
        setattr(sh, kind, built)
        return built

    def sharded_coo(self, view) -> ShardedKind:
        """The view's per-device padded (src, dst, valid) COO bundles."""
        return self._sharded_kind(view, "coo")

    def sharded_blocks(self, view) -> ShardedKind:
        """The view's per-device padded (src, rows, length) leaf-tile bundles."""
        return self._sharded_kind(view, "blocks")

    # -- collectives ---------------------------------------------------------
    def _fn(self, key: tuple, build: Callable) -> Callable:
        fn = self._fn_cache.get(key)
        if fn is None:
            fn = build()
            self._fn_cache[key] = fn
        return fn

    def _count_call(self) -> None:
        with self._lock:
            self.stats.collective_calls += 1

    def pagerank(self, view, iters: int = 10, damping: float = 0.85):
        """Collective PageRank over pinned shard tiles (module docstring
        covers the pull-vs-push choice and the bitwise contract)."""
        import jax

        from . import distributed

        coo = self.sharded_coo(view)
        n = view.n_vertices
        pull = self.symmetric
        self._count_call()
        fn = self._fn(
            ("pr", n, coo.cap, iters, float(damping), pull),
            lambda: jax.jit(
                distributed.make_pagerank(
                    self.mesh, self.axis, n, iters=iters, damping=damping, pull=pull
                )
            ),
        )
        return fn(*coo.global_arrays(self.mesh, self.axis))

    def bfs(self, view, root: int):
        """Collective level-synchronous BFS (bitwise-equal to ``bfs_view``)."""
        import jax
        import jax.numpy as jnp

        from . import distributed

        coo = self.sharded_coo(view)
        n = view.n_vertices
        self._count_call()
        fn = self._fn(
            ("bfs", n, coo.cap),
            lambda: jax.jit(distributed.make_bfs(self.mesh, self.axis, n)),
        )
        return fn(*coo.global_arrays(self.mesh, self.axis), jnp.int32(root))

    def _shard_edge_operand(self, coo: ShardedKind, w: np.ndarray) -> tuple:
        """Slice a per-edge operand (global COO order) onto the shards.

        Global order is ascending-sid segments; each shard holds its sids'
        segments in ascending order, so per-shard gathers re-use the same
        segment spans.  Uploaded per call (weights change per query) and
        counted in ``stats.operand_uploads``.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        w = np.asarray(w, np.float32)
        g_off = np.zeros(len(coo.seg_counts) + 1, np.int64)
        np.cumsum(coo.seg_counts, out=g_off[1:])
        if len(w) != g_off[-1]:
            raise ValueError(
                f"edge operand length {len(w)} != n_edges {int(g_off[-1])}"
            )
        parts = []
        for shard in coo.shards:
            w_k = (
                np.concatenate(
                    [w[g_off[sid] : g_off[sid + 1]] for sid in shard.sids]
                )
                if len(shard.sids)
                else np.zeros(0, np.float32)
            )
            dev = jax.device_put(w_k, shard.device)
            parts.append(jnp.pad(dev, (0, coo.cap - len(w_k))).reshape(1, coo.cap))
        with self._lock:
            self.stats.operand_uploads += len(parts)
        return jax.make_array_from_single_device_arrays(
            (len(parts), coo.cap), NamedSharding(self.mesh, P(self.axis, None)), parts
        )

    def sssp(self, view, w: np.ndarray, root: int):
        """Collective Bellman-Ford (bitwise-equal to ``sssp_view``); ``w``
        follows the global COO edge order, as for the oracle."""
        import jax
        import jax.numpy as jnp

        from . import distributed

        coo = self.sharded_coo(view)
        n = view.n_vertices
        gw = self._shard_edge_operand(coo, w)
        self._count_call()
        fn = self._fn(
            ("sssp", n, coo.cap),
            lambda: jax.jit(distributed.make_sssp(self.mesh, self.axis, n)),
        )
        return fn(*coo.global_arrays(self.mesh, self.axis), gw, jnp.int32(root))

    def wcc(self, view):
        """Collective WCC: both edge directions propagate locally, ``pmin``
        merges — bitwise-equal to ``wcc_view`` on any store."""
        import jax

        from . import distributed

        coo = self.sharded_coo(view)
        n = view.n_vertices
        self._count_call()
        fn = self._fn(
            ("wcc", n, coo.cap),
            lambda: jax.jit(distributed.make_wcc(self.mesh, self.axis, n)),
        )
        return fn(*coo.global_arrays(self.mesh, self.axis))

    def spmm(self, view, h, n_block: int = 64, v_tile: int = 512):
        """Collective per-vertex SpMM over pinned leaf tiles.

        Each shard runs the same Pallas ``leaf_spmm`` kernel the
        single-device ``spmm_view`` uses over its own tile stream, then
        segment-sums by source vertex; every source vertex lives on exactly
        one shard, so the ``psum`` adds exact zeros — bitwise-equal.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from repro.kernels.spmm import leaf_spmm

        blocks = self.sharded_blocks(view)
        n = view.n_vertices
        ax = self.axis
        self._count_call()

        def build():
            # check_vma off for this one collective: the Pallas kernel's
            # output declares no varying mesh axes, and the interpreter that
            # runs it on CPU cannot mix varying and replicated operands
            @partial(
                shard_map, mesh=self.mesh,
                in_specs=(P(ax, None), P(ax, None, None), P(ax, None), P()),
                out_specs=P(), check_vma=False,
            )
            def sp(srcs, rows, length, hrep):
                srcs, rows = srcs[0], rows[0]
                per_tile = leaf_spmm(rows, hrep, n_block=n_block, v_tile=v_tile)
                # SENTINEL src ids of pad tiles fall out of range -> dropped
                y = jax.ops.segment_sum(per_tile, srcs, num_segments=n)
                return jax.lax.psum(y, ax)

            return jax.jit(sp)

        fn = self._fn(("spmm", n, blocks.cap, view.B, n_block, v_tile), build)
        return fn(*blocks.global_arrays(self.mesh, self.axis),
                  jnp.asarray(h, jnp.float32))


__all__ = [
    "ShardBundle",
    "ShardPlane",
    "ShardPlaneStats",
    "ShardedKind",
    "ShardedViewAssembly",
    "active_plane",
    "degree_balanced_placement",
    "enabled",
    "modulo_placement",
]
