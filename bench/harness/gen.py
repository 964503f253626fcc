"""Graph and request generators, all drawn from the run's seed.

The base graph is made on the device in one jitted call: endpoints drawn
with ``jax.random``, sorted by (src, dst), and flagged for self-loops and
duplicates, which are then dropped on the host.  Two generators:

- ``kronecker``: the Graph500 Kronecker generator (initiator a, b, c,
  ``edgefactor * 2**scale`` edges); vertex labels go through a random
  permutation, as the Graph500 specification requires, so the hubs land
  in arbitrary partitions.
- ``uniform``: both endpoints uniform over the vertices.

The write stream and the read requests are drawn with numpy from the same
seed; every seed gets the same number of writes and requests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (``jax.random.key`` keeps
    only the low 32 when 64-bit types are off)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _kronecker(key, scale: int, m: int, a: float, b: float, c: float):
    """Graph500 edge draws: per bit, the source bit is 1 with probability
    c + d, the destination bit follows the initiator's row."""
    ab = a + b
    a_norm = a / ab
    c_norm = c / (1.0 - ab)

    def body(i, carry):
        src, dst = carry
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        r1 = jax.random.uniform(k1, (m,), jnp.float32)
        r2 = jax.random.uniform(k2, (m,), jnp.float32)
        ii = r1 > ab
        jj = r2 > jnp.where(ii, c_norm, a_norm)
        return (src << 1) | ii.astype(jnp.int32), (dst << 1) | jj.astype(jnp.int32)

    zero = jnp.zeros((m,), jnp.int32)
    return jax.lax.fori_loop(0, scale, body, (zero, zero))


def _permutation(perm_key, n: int):
    """The Graph500 relabelling: vertex i is stored as perm[i]."""
    return jax.random.permutation(perm_key, n).astype(jnp.int32)


def _perm_key(seed: int):
    return jax.random.fold_in(root_key(seed), 0x5EED)


def vertex_permutation(seed: int, scale: int) -> np.ndarray:
    return np.asarray(jax.jit(_permutation, static_argnums=1)(_perm_key(seed), 1 << scale))


@partial(jax.jit, static_argnames=("kind", "scale", "m", "a", "b", "c", "permute"))
def _draw(key, perm_key, *, kind, scale, m, a, b, c, permute):
    n = 1 << scale
    if kind == "kronecker":
        src, dst = _kronecker(key, scale, m, a, b, c)
    elif kind == "uniform":
        k1, k2 = jax.random.split(key)
        src = jax.random.randint(k1, (m,), 0, n, jnp.int32)
        dst = jax.random.randint(k2, (m,), 0, n, jnp.int32)
    else:
        raise ValueError(f"unknown generator {kind!r}")
    if permute:
        perm = _permutation(perm_key, n)
        src, dst = perm[src], perm[dst]
    src, dst = jax.lax.sort((src, dst), num_keys=2)
    same = (src[1:] == src[:-1]) & (dst[1:] == dst[:-1])
    keep = (src != dst) & jnp.concatenate([jnp.ones((1,), bool), ~same])
    return src, dst, keep


def draw_edges(graph: dict, seed: int, stream: int, m: int) -> np.ndarray:
    """``m`` draws of the configuration's generator, de-duplicated, without
    self-loops, as sorted int64 keys ``(u << 32) | v``.  ``stream`` 0 is the
    base graph; other streams draw further edges of the same graph (same
    vertex permutation)."""
    src, dst, keep = _draw(
        jax.random.fold_in(root_key(seed), stream), _perm_key(seed),
        kind=graph["generator"], scale=int(graph["scale"]), m=int(m),
        a=float(graph.get("initiator", (0, 0, 0))[0]),
        b=float(graph.get("initiator", (0, 0, 0))[1]),
        c=float(graph.get("initiator", (0, 0, 0))[2]),
        permute=bool(graph.get("permute", False)),
    )
    src, dst, keep = jax.device_get((src, dst, keep))
    return keys_of(src[keep], dst[keep])


def base_edges(graph: dict, seed: int) -> np.ndarray:
    """The deployment's graph: ``edgefactor * 2**scale`` draws."""
    return draw_edges(graph, seed, 0, int(graph["edgefactor"]) << int(graph["scale"]))


def keys_of(u, v) -> np.ndarray:
    return (np.asarray(u, np.int64) << 32) | np.asarray(v, np.int64)


def split_keys(keys: np.ndarray):
    keys = np.asarray(keys, np.int64)
    return keys >> 32, keys & 0xFFFFFFFF


def pairs(keys: np.ndarray) -> np.ndarray:
    u, v = split_keys(keys)
    return np.stack([u, v], 1)


def in_sorted(keys: np.ndarray, sorted_keys: np.ndarray) -> np.ndarray:
    """Membership of ``keys`` in a sorted unique array."""
    if len(sorted_keys) == 0:
        return np.zeros(len(keys), bool)
    i = np.searchsorted(sorted_keys, keys)
    i = np.minimum(i, len(sorted_keys) - 1)
    return sorted_keys[i] == keys


class WriteStream:
    """Logical writes of ``inserts`` fresh edges and ``deletes`` base edges
    each.  No edge is touched twice: inserts are distinct and absent from
    the base graph, deletes are distinct base edges, so the edge set at any
    commit timestamp follows from the stamps alone."""

    def __init__(self, graph, seed, base_keys, n_writes, inserts, deletes):
        rng = np.random.default_rng([int(seed), 1])
        n_ins, n_del = n_writes * inserts, n_writes * deletes
        cand = np.empty(0, np.int64)
        draws = max(4 * n_ins, 1024)
        for stream in range(1, 9):
            more = draw_edges(graph, seed, stream, draws)
            cand = np.union1d(cand, more[~in_sorted(more, base_keys)])
            if len(cand) >= n_ins:
                break
        if len(cand) < n_ins:
            raise RuntimeError(f"write stream: {len(cand)} fresh edges, {n_ins} needed")
        self.ins = rng.permutation(cand)[:n_ins].reshape(n_writes, inserts)
        self.dels = rng.choice(base_keys, size=n_del, replace=False).reshape(n_writes, deletes)
        self.n_writes = n_writes

    def write(self, i: int):
        return pairs(self.ins[i]), pairs(self.dels[i])


def arrivals(rng, rate: float, seconds: float) -> np.ndarray:
    """A Poisson process of ``rate`` over ``seconds``, conditioned on its
    mean count: the same number of arrivals for every seed, at sorted
    uniform times."""
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def search_requests(seed, n_vertices, base_keys, stream: WriteStream, write_due,
                    reads: dict, seconds: float):
    """Open-loop point searches: ``due`` times and (u, v) keys.  A share
    ``hit_share`` are base edges no write deletes (uniform over edges, so
    sources are weighted by degree); ``recent_insert_share`` and
    ``recent_delete_share`` are edges that a write due within ``recent_s``
    of the request, before or after it, inserts or deletes, so that their
    answer depends on the stamp of the view that serves them; the rest are
    random vertex pairs that are no edge at any time.  ``write_due`` is
    each write's due time, or for closed-loop writes the time it is
    expected to commit."""
    rng = np.random.default_rng([int(seed), 2])
    due = arrivals(rng, float(reads["rate_per_s"]), seconds)
    n = len(due)
    shares = [float(reads[k]) for k in ("hit_share", "recent_insert_share",
                                        "recent_delete_share")]
    counts = [int(round(n * s)) for s in shares]
    kind = rng.permutation(np.repeat([0, 1, 2, 3], counts + [n - sum(counts)]))
    deleted = np.sort(stream.dels.reshape(-1))
    inserted = np.sort(stream.ins.reshape(-1))
    keys = np.empty(n, np.int64)
    hit = np.nonzero(kind == 0)[0]
    hits = np.empty(0, np.int64)
    while len(hits) < len(hit):
        c = base_keys[rng.integers(0, len(base_keys), 2 * n + 16)]
        hits = np.concatenate([hits, c[~in_sorted(c, deleted)]])
    keys[hit] = hits[: len(hit)]
    # recent: a key of a write due in (due - recent_s, due + recent_s]
    recent_s = float(reads["recent_s"])
    for k, table in ((1, stream.ins), (2, stream.dels)):
        idx = np.nonzero(kind == k)[0]
        lo = np.searchsorted(write_due, due[idx] - recent_s, "right")
        hi = np.searchsorted(write_due, due[idx] + recent_s, "right")
        w = lo + (rng.random(len(idx)) * np.maximum(hi - lo, 1)).astype(np.int64)
        w = np.minimum(w, len(table) - 1)
        keys[idx] = table[w, rng.integers(0, table.shape[1], len(idx))]
    # non-edges: neither in the base graph nor ever inserted
    rest = np.nonzero(kind == 3)[0]
    fill = np.empty(0, np.int64)
    while len(fill) < len(rest):
        c = keys_of(rng.integers(0, n_vertices, 2 * len(rest) + 16),
                    rng.integers(0, n_vertices, 2 * len(rest) + 16))
        fill = np.concatenate([fill, c[~(in_sorted(c, base_keys) | in_sorted(c, inserted))]])
    keys[rest] = fill[: len(rest)]
    return due, keys
