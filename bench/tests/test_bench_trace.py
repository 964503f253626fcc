"""The trace reduction, on hand-made intervals and on a small trace
recorded here on the CPU from a tiny jitted program; the work counters
against hand counts; the peaks table."""

import jax
import jax.numpy as jnp
import pytest

from bench.harness import trace as tr
from bench.harness.readers import roofline_pct
from bench.harness.spec import Spec, peaks


def test_union_and_gaps_by_hand():
    busy = tr.union([(1, 3), (2, 4), (6, 7), (9, 12)], 0, 10)
    assert busy == [[1, 4], [6, 7], [9, 10]]
    assert tr.gaps(busy, 0, 10) == [(0, 1), (4, 6), (7, 9)]
    assert tr.union([(5, 5), (11, 12)], 0, 10) == []


def test_reduce_by_hand():
    ev = {
        "ops": [("a", "m1", 1.0, 2.0), ("b", "m1", 1.5, 2.5), ("a", "m2", 5.0, 5.5)],
        "programs": [("m1", 1.0, 2.5), ("m2", 5.0, 5.5)],
        "host": [(tr.WINDOW, 0.0, 10.0), ("bench.view", 2.5, 4.0),
                 ("bench.edge_search", 3.0, 9.0)],
    }
    s = tr.reduce(ev)
    assert s["window_s"] == 10.0
    assert s["busy_s"] == pytest.approx(1.5 + 0.5)
    assert s["per_op"] == pytest.approx({"a": 1.5, "b": 1.0})
    assert tr.program_seconds(s, "m1") == pytest.approx(1.5)
    # gaps: [0,1] none, [2.5,5] mostly bench.edge_search (2.0 s) over
    # bench.view (1.5 s), [5.5,10] bench.edge_search
    names = dict((round(d, 6), n) for n, d in s["idle_gaps"])
    assert names[4.5] == "bench.edge_search"
    assert names[2.5] == "bench.edge_search"
    assert names[1.0] == "host: none"
    assert [d for _, d in s["idle_gaps"]] == sorted((d for _, d in s["idle_gaps"]), reverse=True)


def test_attribute_prefers_innermost_on_tie():
    host = [("bench.outer", 0.0, 10.0), ("bench.inner", 2.0, 4.0)]
    assert tr.attribute((2.0, 4.0), host) == "bench.inner"


def test_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tr.start(tmp_path)
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("bench.step"):
            for _ in range(3):
                f(x).block_until_ready()
    tr.stop()
    ev = tr.events(tr.newest(tmp_path))
    assert any(n == tr.WINDOW for n, *_ in ev["host"])
    assert ev["ops"], "no device operations found in the trace"
    s = tr.reduce(ev)
    assert 0 < s["busy_s"] <= s["window_s"]
    # busy is the union: never more than the sum of the op times
    assert s["busy_s"] <= sum(s["per_op"].values()) + 1e-9
    assert sum(d for _, d in s["idle_gaps"]) <= s["window_s"] - s["busy_s"] + 1e-9
    assert any("jit" in name for name in s["per_program"])


def test_op_less_device_plane_leaves_host_ops():
    """A loaded TPU runtime adds a device plane with no XLA ops to a CPU
    trace; the CPU's ops on the host plane still count."""
    from types import SimpleNamespace as NS

    def ev(name, t0, t1, **stats):
        return NS(name=name, start_ns=t0 * 1e9, duration_ns=(t1 - t0) * 1e9,
                  stats=list(stats.items()))

    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(tr.WINDOW, 0.0, 4.0), ev("bench.step", 0.5, 3.5),
        ev("dot", 1.0, 2.0, hlo_op="dot.1", hlo_module="jit_f"),
        ev("tanh", 2.0, 2.5, hlo_op="tanh.2", hlo_module="jit_f")])])
    custom = NS(name="/device:CUSTOM:Megascale Trace", lines=[])
    ev_ = tr.events_of([custom, host])
    assert [o[0] for o in ev_["ops"]] == ["dot.1", "tanh.2"]
    s = tr.reduce(ev_)
    assert s["busy_s"] == pytest.approx(1.5) and s["window_s"] == pytest.approx(4.0)
    assert s["per_program"] == {"jit_f": pytest.approx(1.5)}


def test_work_by_hand():
    spec = Spec()
    ops, nbytes = spec.work("pagerank")(E=1000, n=100, iters=10)
    assert ops == 10 * 2 * 1000
    assert nbytes == 10 * (8 * 1000 + 8 * 100)


def test_roofline_by_hand():
    pk = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # bytes bound: 50 bytes / 10 B/s = 5 s least, over 10 s taken
    assert roofline_pct(ops=100, nbytes=50, seconds=10.0, peaks=pk) == pytest.approx(50.0)
    assert roofline_pct(ops=2000, nbytes=50, seconds=40.0, peaks=pk) == pytest.approx(50.0)


def test_peaks_known_and_unknown():
    path = Spec().dir / "peaks.json"
    v5e = peaks(path, "TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks(path, "cpu")
