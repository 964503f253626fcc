"""Device-resident leaf-block tile cache — per-snapshot layer of the
three-layer memo + delta-plane design.

View materialization is memoized at three layers, each exploiting snapshot
immutability:

1. **Per-subgraph host** (:meth:`SubgraphSnapshot.to_coo_global` /
   ``to_leaf_stream_global``): each immutable snapshot computes its
   vectorized arrays once — the leaf layout is the *compacted* stream
   (packed values + lens/keys sidecars, no SENTINEL padding); a commit
   creates new (cold) snapshots only for the subgraphs it touches.
2. **Per-subgraph device** (this module): each snapshot's compacted stream
   is uploaded once (``jax.device_put``) and re-padded to the fixed-B
   ``[n, B]`` tile shape *on the device* (:func:`_pad_tiles_on_device`) —
   the Pallas kernels still see dense tiles, but the bus only ever carries
   live bytes, and only one transfer per snapshot version.  A warm repeat
   query performs **zero** host->device leaf-block transfers.
3. **Per-view delta plane** (:mod:`repro.core.view_assembler`): the global
   concatenated arrays of a view.  A fresh view splices only the dirty
   subgraphs' tiles into its *predecessor view's* concatenated device
   arrays (``jax.lax.dynamic_update_slice`` when segment sizes are
   unchanged, an O(dirty)-run concat otherwise), so post-write assembly is
   O(dirty) device work instead of the O(S) re-concatenation this module's
   :func:`assemble_leaf_blocks` performs.  A view with
   no usable predecessor uploads the host's concatenated arrays once and
   re-pads them with one program, so per-subgraph tiles exist only for
   snapshots a splice brought in.  :func:`assemble_leaf_blocks` remains
   as the non-delta full-concat reference used by benchmarks to quantify
   the splice win.  The assembler's dirty
   uploads go through :func:`leaf_block_tiles` / :func:`coo_tiles` with
   ``wait=False`` — async prefetch: per-subgraph ``device_put`` is issued
   as soon as each host tile is ready, overlapping transfer with the host
   materialization of the remaining dirty subgraphs.

Lifecycle contract (release / GC invalidation)
----------------------------------------------

Device tiles follow the exact lifecycle of the host caches they mirror:

1. **Birth** — the first device request on a snapshot uploads that snapshot's
   host-memoized arrays once (``jax.device_put``) and pins them on the
   snapshot object.  The host arrays are themselves *copies* of the
   :class:`~repro.core.leaf_pool.LeafPool` rows, so neither cache layer ever
   aliases recyclable pool memory.
2. **Sharing** — snapshots are immutable once published; every view that
   resolves the same version shares the same device tiles.  After a commit
   dirtying ``d`` of ``S`` subgraphs, only the ``d`` fresh snapshots upload.
3. **Death** — :meth:`SubgraphSnapshot.release` (writer-driven GC reclaiming
   a version) drops the device tiles together with the host caches and marks
   the snapshot *released*.  Releasing is a correctness event, not merely a
   memory optimization: GC returns the version's pool rows to the free list,
   after which the pool may recycle them for unrelated neighbor sets.  A
   released snapshot therefore **refuses** to re-materialize (RuntimeError)
   instead of silently rebuilding tiles from recycled rows — a recycled
   ``LeafPool`` row can never serve a stale tile.
4. **Audit** — each upload stamps the pool row *generations* backing the
   snapshot's directories (:func:`tiles_fresh`).  The pool bumps a row's
   generation whenever the row is freed, so a live snapshot's stamp is
   invariant (its refcounts keep the rows alive) and a violated stamp is
   direct evidence of a stale tile.  Tests and the concurrency stress
   harness assert this after every GC cycle.

Accounting: resident device bytes are charged to
:meth:`RapidStore.memory_bytes` via ``SubgraphSnapshot.device_cache_bytes``,
and module-level :data:`stats` counts hits / misses / uploads / bytes so
tests (and benchmarks) can assert the zero-transfer warm path.
"""

from __future__ import annotations

import functools
import os
import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import numpy as np

from ..obs import metrics as _metrics
from ..obs.trace import TRACER as _trc


# ---------------------------------------------------------------------------
# Cache statistics — the observable transfer contract
# ---------------------------------------------------------------------------
class CacheStats:
    """Counters for the device tile cache (process-wide, lock-protected).

    ``uploads`` counts ``jax.device_put`` calls on leaf-block / COO arrays —
    the acceptance criterion "warm repeat performs zero host->device
    transfers" is asserted as ``uploads`` staying flat across the repeat.

    Backed by :mod:`repro.obs.metrics` counters (``device_cache_<field>`` on
    the process registry), so the same values feed Prometheus exports and
    ``telemetry_report()``; each increment holds the field's counter lock,
    so concurrent readers racing on hit/miss paths never lose counts.
    """

    _FIELDS = ("hits", "misses", "uploads", "bytes_uploaded", "releases")

    def __init__(self, registry: Optional[_metrics.MetricsRegistry] = None) -> None:
        reg = registry if registry is not None else _metrics.REGISTRY
        self._c = {f: reg.counter("device_cache_" + f) for f in self._FIELDS}

    def __getattr__(self, name: str):
        c = self.__dict__["_c"].get(name)
        if c is None:
            raise AttributeError(name)
        return c.value

    def add(self, name: str, delta: int = 1) -> None:
        self._c[name].add(delta)

    def hit_ratio(self) -> float:
        """Fraction of tile requests served without an upload (0.0 when idle)."""
        h, m = self._c["hits"].value, self._c["misses"].value
        return h / (h + m) if (h + m) else 0.0

    def reset(self) -> None:
        for c in self._c.values():
            c.reset()

    def snapshot(self) -> Tuple[int, int, int, int, int]:
        return tuple(self._c[f].value for f in self._FIELDS)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        body = ", ".join(f"{f}={self._c[f].value}" for f in self._FIELDS)
        return f"CacheStats({body})"


stats = CacheStats()
_metrics.REGISTRY.gauge("device_cache_hit_ratio", fn=stats.hit_ratio)
# Serializes the miss path: without it two readers racing on a fresh
# snapshot would both materialize + upload (benign data-wise — snapshots are
# immutable — but it double-counts stats and transiently doubles device
# memory).  Hits stay lock-free.
_mat_lock = threading.Lock()


def enabled() -> bool:
    """Device-cache routing switch (``REPRO_DISABLE_DEVICE_CACHE`` opts out)."""
    return not os.environ.get("REPRO_DISABLE_DEVICE_CACHE")


def _device_put(host_arrays: Sequence[np.ndarray], wait: bool = True,
                device=None) -> tuple:
    """Upload ``host_arrays`` (to ``device``, else the default device) and
    count the transfer."""
    import jax

    out = tuple(jax.device_put(a, device) for a in host_arrays)
    if wait:
        for o in out:
            o.block_until_ready()
    stats.add("uploads", len(host_arrays))
    # charge the *device* bytes: device_put canonicalizes int64 -> int32
    # under default x64-disabled JAX, halving the resident size
    stats.add("bytes_uploaded", int(sum(o.nbytes for o in out)))
    return out


def _hit() -> None:
    stats.add("hits")


def _miss() -> None:
    stats.add("misses")


# ---------------------------------------------------------------------------
# Per-snapshot device tiles
# ---------------------------------------------------------------------------
def _gen_stamp(snap) -> Tuple[np.ndarray, np.ndarray]:
    """Capture (leaf row ids, pool generations) backing ``snap``'s dirs.

    Ids are gid-encoded (:meth:`TieredLeafPool.gids`) so tiered pools decode
    them back to the owning subpool; identity on a plain pool.
    """
    if not snap.dirs:
        e = np.empty(0, np.int64)
        return e, e
    ids = np.concatenate(
        [snap.pool.gids(d.leaf_ids, d.tier) for d in snap.dirs.values()]
    )
    return ids, np.asarray(snap.pool.generation[ids]).copy()


def tiles_fresh(snap) -> bool:
    """True iff ``snap``'s device tiles still describe live pool rows.

    A live (un-released) snapshot's refcounts pin its rows, so its stamp can
    never change — a False return means a stale tile escaped the lifecycle
    contract.  Snapshots without device tiles are vacuously fresh.
    """
    stamp = getattr(snap, "_dev_gen_stamp", None)
    if stamp is None:
        return True
    ids, gens = stamp
    return bool(np.array_equal(np.asarray(snap.pool.generation[ids]), gens))


def _pad_tiles_on_device(data, lens, B: int):
    """Re-pad packed leaf values to the fixed-B ``[n, B]`` tiles on device.

    The device twin of :func:`repro.core.subgraph.pad_leaf_stream`: the
    host->device transfer carries only the compacted stream (live values +
    sidecars); the SENTINEL tail the Pallas kernels expect is synthesized
    where the tiles live.  Runs on whatever device ``data``/``lens`` are
    committed to, as one compiled program per input shape.
    """
    import jax
    import jax.numpy as jnp

    from .leaf_pool import SENTINEL

    tok = _trc.begin()
    n = int(lens.shape[0])
    with _trc.site("tier_repad"):
        if int(data.shape[0]) == 0:
            # no live values (possibly no tiles at all): pure-SENTINEL
            # tiles.  Zero-element results fall off their committed device
            # (jax places any 0-sized output on the default device), and
            # this tuple is cached per-(snapshot, device) — re-commit
            # explicitly.
            out = jax.device_put(
                jnp.broadcast_to(lens[:, None] * 0 + jnp.int32(SENTINEL), (n, B)),
                next(iter(lens.devices())),
            )
        else:
            out = _pad_tiles_program()(data, lens, B=B)
    if tok:
        _trc.end(tok, "tier_repad", cat="read", args={"n_tiles": n, "B": B})
    return out


def _pad_tiles_impl(data, lens, B: int):
    import jax.numpy as jnp

    from .leaf_pool import SENTINEL

    n = lens.shape[0]
    off = jnp.cumsum(lens) - lens
    col = jnp.arange(B, dtype=lens.dtype)
    mask = col[None, :] < lens[:, None]
    safe = jnp.where(mask, off[:, None] + col[None, :], 0)
    return jnp.where(
        mask, jnp.take(data, safe.reshape(-1)).reshape(n, B), jnp.int32(SENTINEL)
    )


@functools.cache
def _pad_tiles_program():
    """The jitted re-pad, built on first use (this module stays importable
    without initializing JAX)."""
    import jax

    return jax.jit(_pad_tiles_impl, static_argnames=("B",))


def split_stream_by_tier(data, lens, keys, tiers):
    """Split a packed leaf stream into per-tier packed sub-streams (host).

    Returns ``{tier: (gidx, data_t, lens_t, keys_t)}`` where ``gidx`` holds
    the ascending global leaf positions of that tier's leaves in the input
    stream — the scatter map the per-tier device groups carry so consumers
    can route global leaf indices to the right ``[n_t, B_t]`` group.
    """
    lens64 = np.asarray(lens).astype(np.int64)
    off = np.cumsum(lens64) - lens64
    out = {}
    for t in np.unique(np.asarray(tiers)):
        gidx = np.nonzero(np.asarray(tiers) == t)[0]
        sel = lens64[gidx]
        local_off = np.cumsum(sel) - sel
        pos = np.arange(int(sel.sum()), dtype=np.int64) - np.repeat(local_off, sel)
        data_t = data[np.repeat(off[gidx], sel) + pos]
        out[int(t)] = (gidx, data_t, lens[gidx], keys[gidx])
    return out


def leaf_block_tiles(snap, wait: bool = True):
    """Device-resident leaf tiles of one snapshot.

    Single-tier pools: the ``(src, rows, length)`` tuple of old — the
    host-memoized *compacted* stream is uploaded (packed values, lens, keys;
    no SENTINEL padding crosses the bus) then re-padded to the fixed-B
    ``[n, B]`` tile shape device-side; one transfer per snapshot version,
    ever.  Tiered pools: a :class:`DeviceTieredBlocks` — the packed stream
    is split per tier host-side, each tier's sub-stream uploads separately,
    and one device-side re-pad per tier yields fixed ``[n_t, B_t]`` groups
    (so the Pallas kernels keep fixed shapes per tier, and the resident tile
    bytes shrink to each leaf's native width).  Memoized on the snapshot
    either way; raises RuntimeError on released snapshots.

    ``wait=False`` skips the post-upload ``block_until_ready`` — the delta
    plane's async prefetch path issues non-blocking ``jax.device_put`` calls
    per dirty subgraph so the transfer overlaps the next subgraph's host
    materialization; JAX sequences any downstream use automatically.
    """
    cached = snap._dev_blocks_cache
    if cached is not None:
        _hit()
        return cached
    with _mat_lock:
        cached = snap._dev_blocks_cache
        if cached is not None:  # lost the race: another reader just uploaded
            _hit()
            return cached
        _miss()
        # raises if released; the stream is a copy of the pool rows
        data, _offsets, lens, keys, tiers = snap.to_leaf_stream_global()
        if len(snap.pool.tiers) == 1:
            up_data, up_lens, up_keys = _device_put((data, lens, keys), wait=wait)
            rows = _pad_tiles_on_device(up_data, up_lens, snap.pool.B)
            tiles = (up_keys, rows, up_lens)
        else:
            groups = {}
            gidx = {}
            for t, (gi, d_t, l_t, k_t) in split_stream_by_tier(
                data, lens, keys, tiers
            ).items():
                up_d, up_l, up_k = _device_put((d_t, l_t, k_t), wait=wait)
                groups[t] = (up_k, _pad_tiles_on_device(up_d, up_l, t), up_l)
                gidx[t] = gi
            tiles = DeviceTieredBlocks(
                groups=groups, gidx=gidx, n_blocks=len(lens), B=snap.pool.B
            )
        snap._dev_gen_stamp = _gen_stamp(snap)
        snap._dev_blocks_cache = tiles
        return tiles


def coo_tiles(snap, wait: bool = True) -> tuple:
    """Device-resident ``(src, dst)`` COO tiles of one snapshot (memoized).

    ``wait=False`` prefetches without blocking (see :func:`leaf_block_tiles`).
    """
    cached = snap._dev_coo_cache
    if cached is not None:
        _hit()
        return cached
    with _mat_lock:
        cached = snap._dev_coo_cache
        if cached is not None:
            _hit()
            return cached
        _miss()
        host = snap.to_coo_global()
        tiles = _device_put(host, wait=wait)
        if snap._dev_gen_stamp is None:
            snap._dev_gen_stamp = _gen_stamp(snap)
        snap._dev_coo_cache = tiles
        return tiles


def note_release(snap) -> None:
    """Record (for stats) that a snapshot's device tiles died with GC."""
    if (
        snap._dev_blocks_cache is not None
        or snap._dev_coo_cache is not None
        or snap._shard_dev_cache
    ):
        stats.add("releases")


# ---------------------------------------------------------------------------
# Per-(snapshot, device) shard tiles — the shard plane's residency layer.
#
# Same lifecycle as the default-device tiles above (upload once per snapshot
# version, generation-stamped against recycled LeafPool rows, dropped in
# release()), but pinned to an EXPLICIT device: the shard plane
# (repro.core.shard_plane) places each subgraph's tiles on the device its
# placement policy chose, so a commit dirtying subgraphs on one shard
# uploads only to that shard's device.  The functions return
# ``(tiles, uploaded_bytes)`` — 0 bytes on a hit — so the plane can keep
# per-shard upload counters on top of the process-wide ``stats``.
# ---------------------------------------------------------------------------
def _shard_cache_put(snap, key, host_arrays, device, wait, finish=None):
    """Upload ``host_arrays`` to ``device``, account, stamp, and cache.

    ``finish``, when given, maps the uploaded tuple to the cached tile
    tuple (e.g. the leaf path's device-side re-pad) — the transfer byte
    count always reflects only what actually crossed the bus.
    """
    import jax

    up = tuple(jax.device_put(a, device) for a in host_arrays)
    if wait:
        for t in up:
            t.block_until_ready()
    nbytes = int(sum(int(t.nbytes) for t in up))
    stats.add("uploads", len(host_arrays))
    stats.add("bytes_uploaded", nbytes)
    tiles = up if finish is None else finish(up)
    if snap._shard_dev_cache is None:
        snap._shard_dev_cache = {}
    if snap._dev_gen_stamp is None:
        snap._dev_gen_stamp = _gen_stamp(snap)
    snap._shard_dev_cache[key] = tiles
    return tiles, nbytes


def shard_coo_tiles(snap, device, wait: bool = True) -> Tuple[tuple, int]:
    """``(src, dst)`` COO tiles of one snapshot pinned on ``device``.

    Memoized per (snapshot, device); returns ``(tiles, uploaded_bytes)``
    with 0 bytes on a hit.  Raises RuntimeError on released snapshots (the
    pool may have recycled their rows — see the lifecycle contract above).
    """
    key = ("coo", device.id)
    cache = snap._shard_dev_cache
    if cache is not None and key in cache:
        _hit()
        return cache[key], 0
    with _mat_lock:
        cache = snap._shard_dev_cache
        if cache is not None and key in cache:
            _hit()
            return cache[key], 0
        _miss()
        host = snap.to_coo_global()  # raises if released; copies pool rows
        return _shard_cache_put(snap, key, host, device, wait)


def shard_leaf_tiles(snap, device, wait: bool = True) -> Tuple[tuple, int]:
    """``(src, rows, length)`` leaf-block tiles pinned on ``device``.

    Same contract as :func:`shard_coo_tiles`; like the default-device path,
    only the snapshot's *compacted* stream crosses the bus — the fixed-B
    padding is synthesized on the shard device after the upload, so the
    returned ``uploaded_bytes`` counts packed bytes only.
    """
    key = ("blocks", device.id)
    cache = snap._shard_dev_cache
    if cache is not None and key in cache:
        _hit()
        return cache[key], 0
    with _mat_lock:
        cache = snap._shard_dev_cache
        if cache is not None and key in cache:
            _hit()
            return cache[key], 0
        _miss()
        data, _offsets, lens, keys, _tiers = snap.to_leaf_stream_global()
        return _shard_cache_put(
            snap, key, (data, lens, keys), device, wait,
            finish=lambda up: (
                up[2], _pad_tiles_on_device(up[0], up[1], snap.pool.B), up[1]
            ),
        )


# ---------------------------------------------------------------------------
# Migration staging — the SEND/RECV/FREE halves of the reshard runtime
# (repro.core.reshard).  SEND uploads WITHOUT installing into the snapshot
# cache, so an aborted migration leaves no trace; RECV commits the staged
# tiles under the same lock + generation stamp the normal fetch path uses;
# FREE drops a device's entries after the placement flip (any straggler
# reader at the old placement just re-uploads — correctness is unaffected,
# only the one transfer is repaid).
# ---------------------------------------------------------------------------
def stage_shard_tiles(snap, device, kind: str, wait: bool = False):
    """SEND: upload one snapshot's ``kind`` tiles to ``device``, unstaged.

    Returns ``(key, tiles, uploaded_bytes)``; 0 bytes when the tiles are
    already resident (the migration then degenerates to a cache no-op).
    Raises RuntimeError on a released snapshot, like the fetch paths.
    """
    import jax

    key = (kind, device.id)
    cache = snap._shard_dev_cache
    if cache is not None and key in cache:
        return key, cache[key], 0
    if kind == "coo":
        host = snap.to_coo_global()
        up = tuple(jax.device_put(a, device) for a in host)
        tiles = up
    else:
        data, _offsets, lens, keys, _tiers = snap.to_leaf_stream_global()
        up = tuple(jax.device_put(a, device) for a in (data, lens, keys))
        tiles = (up[2], _pad_tiles_on_device(up[0], up[1], snap.pool.B), up[1])
    if wait:
        for t in up:
            t.block_until_ready()
    nbytes = int(sum(int(t.nbytes) for t in up))
    stats.add("uploads", len(up))
    stats.add("bytes_uploaded", nbytes)
    return key, tiles, nbytes


def install_shard_tiles(snap, key, tiles) -> None:
    """RECV: commit staged tiles into the per-(snapshot, device) cache.

    ``setdefault`` under the materialization lock: if a concurrent view
    assembly already uploaded the same (snapshot, device) entry, its tiles
    win and the staged copy is dropped — both are bitwise-identical
    materializations of the same immutable snapshot.
    """
    with _mat_lock:
        if snap._shard_dev_cache is None:
            snap._shard_dev_cache = {}
        if snap._dev_gen_stamp is None:
            snap._dev_gen_stamp = _gen_stamp(snap)
        snap._shard_dev_cache.setdefault(key, tiles)


def drop_shard_tiles(snap, device, kinds=("coo", "blocks")) -> int:
    """FREE: drop ``snap``'s cache entries pinned on ``device``.

    Returns the bytes released.  Safe against concurrent readers: pinned
    view bundles hold the tile arrays directly, so dropping the cache entry
    only forces a future assembly at the old placement to re-upload.
    """
    freed = 0
    with _mat_lock:
        cache = snap._shard_dev_cache
        if cache:
            for kind in kinds:
                tiles = cache.pop((kind, device.id), None)
                if tiles is not None:
                    freed += int(sum(int(t.nbytes) for t in tiles))
    return freed


# ---------------------------------------------------------------------------
# View-level assembly: O(dirty) upload + O(S) device concat.
# This is the NON-DELTA reference path: SnapshotView.to_*_device route
# through repro.core.view_assembler (which splices against the predecessor
# view and falls back to an equivalent of these when no predecessor exists);
# benchmarks call these directly to time the full-concat baseline.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class DeviceLeafBlockView:
    """Device twin of :class:`~repro.core.snapshot.LeafBlockView`."""

    src: object  # jax.Array int32 [n_blocks]
    rows: object  # jax.Array int32 [n_blocks, B]
    length: object  # jax.Array int32 [n_blocks]

    @property
    def n_blocks(self) -> int:
        return int(self.src.shape[0])


@dataclass(frozen=True)
class DeviceTieredBlocks:
    """Per-tier device leaf tiles of a tiered pool.

    Each tier's leaves live in their own fixed-shape group — ``groups[t] =
    (src, rows [n_t, t], length)`` jax.Arrays padded device-side to that
    tier's native width — so the Pallas kernels dispatch once per tier with
    a fixed ``[*, B_t]`` shape and resident tile bytes track each leaf's
    real width instead of the max tier.  ``gidx[t]`` (host int64, ascending)
    maps each group row back to its global position in the unified leaf
    stream order; consumers gathering by global leaf index
    (edge search / intersect) ``searchsorted`` into it to find the group
    row.  ``src``/``rows``/``length`` lazily build the unified
    max-width twin for compatibility consumers and parity asserts.
    """

    groups: dict  # tier -> (src, rows, length) jax.Arrays
    gidx: dict  # tier -> np.ndarray int64 global leaf positions (ascending)
    n_blocks: int
    B: int  # unified compat padding width (max tier)
    _unified: list = field(default_factory=list, repr=False, compare=False)

    @property
    def tiers(self):
        return sorted(self.groups)

    def _build_unified(self) -> tuple:
        import jax.numpy as jnp

        from .leaf_pool import SENTINEL

        src = jnp.zeros(self.n_blocks, jnp.int32)
        rows = jnp.full((self.n_blocks, self.B), jnp.int32(SENTINEL))
        length = jnp.zeros(self.n_blocks, jnp.int32)
        for t in self.tiers:
            s, r, l = self.groups[t]
            gi = jnp.asarray(self.gidx[t], jnp.int32)
            pad = self.B - int(r.shape[1])
            if pad:
                r = jnp.pad(r, ((0, 0), (0, pad)), constant_values=SENTINEL)
            src = src.at[gi].set(s)
            rows = rows.at[gi].set(r)
            length = length.at[gi].set(l)
        return src, rows, length

    @property
    def unified(self) -> tuple:
        if not self._unified:
            self._unified.append(self._build_unified())
        return self._unified[0]

    @property
    def src(self):
        return self.unified[0]

    @property
    def rows(self):
        return self.unified[1]

    @property
    def length(self):
        return self.unified[2]

    def device_bytes(self) -> int:
        total = 0
        for cols in self.groups.values():
            total += sum(int(a.nbytes) for a in cols)
        if self._unified:
            total += sum(int(a.nbytes) for a in self._unified[0])
        return total


@dataclass(frozen=True)
class DeviceCSRView:
    """Device twin of :class:`~repro.core.snapshot.CSRView`."""

    offsets: object  # jax.Array [n_vertices + 1]
    # jax.Array int32; a view's is its capacity-form COO dst column, whose
    # entries past offsets[-1] are SENTINEL
    indices: object


def assemble_leaf_blocks(snaps: Sequence, B: int) -> DeviceLeafBlockView:
    """Concatenate per-snapshot device tiles into the global tile stream."""
    import jax.numpy as jnp

    parts = [leaf_block_tiles(s) for s in snaps]
    if not parts:
        z = np.zeros(0, np.int32)
        src, rows, length = _device_put((z, np.zeros((0, B), np.int32), z))
        return DeviceLeafBlockView(src, rows, length)
    cols = [
        (p.src, p.rows, p.length) if isinstance(p, DeviceTieredBlocks) else p
        for p in parts
    ]
    return DeviceLeafBlockView(
        jnp.concatenate([c[0] for c in cols]),
        jnp.concatenate([c[1] for c in cols]),
        jnp.concatenate([c[2] for c in cols]),
    )


__all__ = [
    "CacheStats",
    "DeviceCSRView",
    "DeviceLeafBlockView",
    "DeviceTieredBlocks",
    "split_stream_by_tier",
    "assemble_leaf_blocks",
    "coo_tiles",
    "enabled",
    "leaf_block_tiles",
    "note_release",
    "shard_coo_tiles",
    "shard_leaf_tiles",
    "stats",
    "tiles_fresh",
]
