"""Controls and planted faults: the same run with what the store produces
replaced, so that the comparison is seen to fail.

- PageRank control: the reference algorithm in the program's place,
  computed in bfloat16, the precision below the configuration's float32.
- Ingest control: each write is acknowledged with its last delete never
  submitted, breaking the durability guarantee (an acknowledged write is
  read back whole).
"""

from __future__ import annotations

import numpy as np


def fns(traffic, search_fn, analytics_fn):
    """(search_fn, analytics_fn, fault or None) of the cell's control: the
    write fault where writes are what the cell measures, else the bfloat16
    PageRank."""
    if traffic.get("primary", "reads") == "writes":
        return search_fn, analytics_fn, Fault("drop_delete")
    if traffic["reads"]["op"] != "pagerank":
        raise ValueError(f"no control for reads of {traffic['reads']['op']!r}")

    def analytics(view, r):
        src, dst = view.to_coo_device()
        return pagerank_bf16(src, dst, n=view.n_vertices, iters=int(r["iters"]),
                             damping=float(r["damping"]))

    return search_fn, analytics, None


def _pagerank_bf16(src, dst, n, iters, damping):
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    deg = jax.ops.segment_sum(jnp.ones(src.shape, bf), src, num_segments=n)
    inv = jnp.where(deg > 0, bf(1) / jnp.maximum(deg, bf(1)), bf(0))
    p = jnp.full((n,), bf(1.0 / n), bf)
    d = bf(damping)
    for _ in range(iters):
        agg = jax.ops.segment_sum((p * inv)[src], dst, num_segments=n)
        dangling = jnp.sum(jnp.where(deg == 0, p, bf(0)))
        p = (bf(1) - d) / bf(n) + d * (agg + dangling / bf(n))
    return p.astype(jnp.float32)


_JIT = []


def pagerank_bf16(src, dst, *, n, iters, damping):
    import jax

    if not _JIT:
        _JIT.append(jax.jit(_pagerank_bf16, static_argnames=("n", "iters", "damping")))
    return _JIT[0](src, dst, n=n, iters=iters, damping=damping)


class Fault:
    """A fault planted where the answer is produced: ``answers`` (a batch
    of search answers), ``ranks`` (a PageRank result), ``write`` (the ops
    of a write as submitted) and ``view`` (the view a reader answers from)
    may each alter what passes through.  ``stale_view`` answers every read
    from the snapshot pinned at the window's start, while the read keeps
    the stamp of the snapshot it pinned: a view behind its stamp."""

    def __init__(self, kind: str):
        self.kind = kind
        self.fired = 0
        self._held = None

    def start(self, store):
        if self.kind == "stale_view":
            self._held = store.begin_read()

    def close(self, store):
        if self._held is not None:
            store.end_read(self._held)
            self._held = None

    def view(self, store, handle):
        if self._held is None:
            return handle.view
        if self._held.ts != handle.ts:
            self.fired += 1
        return self._held.view

    def answers(self, first: int, got):
        got = np.array(got, bool)
        if self.kind == "flip_one" and self.fired == 0 and len(got):
            got[0] = ~got[0]
            self.fired += 1
        elif self.kind == "half_batch":
            # half of each batch left out: answered "absent" unread
            got[len(got) // 2:] = False
            self.fired += 1
        return got

    def ranks(self, k: int, res):
        res = np.array(res)
        if self.kind == "alter_rank" and self.fired == 0:
            res[int(np.argmax(res))] *= 1.01
            self.fired += 1
        return res

    def write(self, j: int, ins, dels):
        if self.kind == "drop_delete" and len(dels):
            self.fired += 1
            return ins, dels[:-1]
        if self.kind == "half_write":
            self.fired += 1
            return ins[: len(ins) // 2], dels[: len(dels) // 2]
        return ins, dels
