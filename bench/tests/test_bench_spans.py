"""The readers of the program's own spans (``bench/harness/spans.py`` and
the five metrics over it): by hand on synthetic spans, and on traced CPU
rehearsals of both cells, where the spans are also checked against the
read spans and the profile's clock."""

from types import SimpleNamespace as NS

import pytest

from bench.harness import trace as tr
from bench.harness.main import run_cell
from bench.harness.spec import Spec
from bench.tests.conftest import SEED, SMALL

NEW = ["splice_ms.ingest", "splice_ms.pagerank", "splice_compiles.ingest",
       "pause_share.ingest", "quiesce_ms.pagerank"]
EPOCH = 10**12


def _span(name, start_s, dur_s, ts=-1, **args):
    return NS(name=name, start_ns=EPOCH + int(start_s * 1e9), dur_ns=int(dur_s * 1e9),
              ts=ts, args=args)


@pytest.fixture
def fake_ring(monkeypatch):
    """Put hand-made spans where the readers look for the program's."""
    import repro.obs.trace as trace_mod

    def use(spans, enabled=True, epoch=EPOCH):
        fake = NS(enabled=enabled, epoch_ns=epoch, spans=lambda: list(spans))
        monkeypatch.setattr(trace_mod, "TRACER", fake)

    return use


def _read(metric, run):
    return Spec().reader(metric)(run)


def test_splice_ms_is_a_mean_per_view_clipped_to_the_window(fake_ring):
    fake_ring([
        _span("view_splice", -1.0, 2.0, ts=1),     # 1 s inside
        _span("view_splice", 3.0, 1.0, ts=2),      # leaf tiles and COO of one view
        _span("view_splice", 4.0, 2.0, ts=2),
        _span("view_splice", 9.0, 5.0, ts=3),      # 1 s inside
        _span("view_splice", 12.0, 1.0, ts=4),     # after the window
        _span("read", 0.0, 10.0, ts=2),
    ])
    run = NS(window_s=10.0)
    for m in ("splice_ms.ingest", "splice_ms.pagerank"):
        assert _read(m, run) == pytest.approx(1e3 * (1.0 + 3.0 + 1.0) / 3)


def test_splice_compiles_counts_the_sites_compiles_in_the_window(fake_ring):
    fake_ring([
        _span("view_splice", 1.0, 2.0, ts=1),
        _span("compile", 1.1, 0.1, site="view_splice", sites=("view_splice",)),
        # a re-pad inside the splice counts with it
        _span("compile", 1.5, 0.1, site="tier_repad", sites=("view_splice", "tier_repad")),
        _span("compile", 2.0, 0.1, site="view_splice", sites=("view_splice",)),
        _span("compile", 2.5, 0.1, site="tier_repad", sites=("tier_repad",)),
        _span("compile", -0.5, 0.1, site="view_splice", sites=("view_splice",)),  # before
        _span("compile", 11.0, 0.1, site="view_splice", sites=("view_splice",)),  # after
        _span("compile", 3.0, 0.1, site=None, sites=()),
    ])
    assert _read("splice_compiles.ingest", NS(window_s=10.0)) == 3.0


def test_splice_compiles_without_a_splice_in_the_window(fake_ring):
    fake_ring([_span("view_splice", 12.0, 1.0),
               _span("compile", 12.1, 0.1, site="view_splice", sites=("view_splice",))])
    assert _read("splice_compiles.ingest", NS(window_s=10.0)) is None


def test_pause_share_leaves_out_the_drain(fake_ring):
    fake_ring([
        _span("quiesce", 1.0, 3.0, drain_s=1.0),    # paused 2-4: 2 s
        _span("quiesce", -2.0, 3.0, drain_s=0.5),   # paused -1.5-1: 1 s inside
        _span("quiesce", 9.0, 4.0, drain_s=0.5),    # paused 9.5-13: 0.5 s inside
    ])
    assert _read("pause_share.ingest", NS(window_s=10.0)) == pytest.approx(35.0)


def test_quiesce_ms_until_the_first_pin(fake_ring):
    fake_ring([
        _span("quiesce", -1.0, 2.0, drain_s=0.1),   # 1 s after the start
        _span("quiesce", 5.0, 2.0, drain_s=0.1),    # 1 s before the pin at 6
        _span("quiesce", 8.0, 1.0, drain_s=0.1),
    ])
    run = NS(window_s=40.0, cycles=[(6.0, 13.0, 40.0, {})])
    assert _read("quiesce_ms.pagerank", run) == pytest.approx(2000.0)
    fake_ring([_span("quiesce", 8.0, 1.0, drain_s=0.1)])
    assert _read("quiesce_ms.pagerank", run) == 0.0


@pytest.mark.parametrize("metric", NEW)
def test_nothing_recorded_reads_none(fake_ring, metric):
    run = NS(window_s=10.0, cycles=[(6.0, 13.0, 40.0, {})])
    fake_ring([])
    assert _read(metric, run) is None
    # tracing off, or a program whose tracer keeps no window clock
    full = [_span("view_splice", 1.0, 1.0, ts=1), _span("quiesce", 1.0, 1.0, drain_s=0.1),
            _span("compile", 1.5, 0.1, site="view_splice", sites=("view_splice",))]
    fake_ring(full, enabled=False)
    assert _read(metric, run) is None
    fake_ring(full, epoch=None)
    assert _read(metric, run) is None


@pytest.mark.parametrize("cell", ["uniform-21.ingest", "g500-22.pagerank"])
def test_traced_rehearsal_spans(cell, tmp_path, monkeypatch):
    """All of the cell's new metrics read a number; every view_splice lies
    inside a read span of its view and thread; the clock anchors in the
    traced slice agree within 50 us."""
    from jax.profiler import ProfileData

    from bench.harness import spec as spec_mod
    from repro.obs import profile
    from repro.obs.trace import TRACER

    monkeypatch.setattr(spec_mod.Spec, "peaks",
                        lambda self, kind: spec_mod.peaks(self.dir / "peaks.json", "TPU v5 lite"))
    kept = {}
    reduce = tr.reduce

    def keep(ev, top=10):
        kept["spans"], kept["epoch_ns"] = TRACER.spans(), TRACER.epoch_ns
        return reduce(ev, top)

    monkeypatch.setattr(tr, "reduce", keep)
    over = {"config": SMALL["config"],
            "traffic": dict(SMALL["traffic"], trace={"after_s": 0.0, "seconds": 1.0})}
    r = run_cell(cell, SEED, 2.0, True, require_chip=False, overrides=over,
                 out_dir=tmp_path, compile_cache=False)
    assert r["correct"] is True, r["checks"]
    mine = {m["name"] for m in Spec().metrics(cell, True)} & set(NEW)
    assert mine and all(isinstance(r["metrics"][m]["value"], float) for m in mine)
    spans = kept["spans"]
    reads = [s for s in spans if s.name == "read"]
    splices = [s for s in spans if s.name == "view_splice"]
    assert splices
    for s in splices:
        assert any(q.ts == s.ts and q.tid == s.tid and q.start_ns <= s.start_ns
                   and s.start_ns + s.dur_ns <= q.start_ns + q.dur_ns for q in reads), s
    path = tr.newest(tmp_path / "trace")
    host = profile.host_events(ProfileData.from_file(path).planes)
    offsets = profile.clock_offsets(host, spans)
    assert offsets and max(offsets) - min(offsets) < 50e-6
    # the script's attribution of the slice's idle time
    idle = _script("idle_by_span").idle_by_span(path, spans, kept["epoch_ns"], top=5)
    assert 0 < idle["idle_s"] <= idle["slice_s"]
    assert idle["idle_s"] == pytest.approx(sum(s for _, s in idle["idle_by_span"]))
    assert len(idle["idle_gaps"]) <= 5 and all(isinstance(n, str) for n, *_ in idle["idle_gaps"])
    assert all(-1.0 < start < _window_end(kept) for _, _, start in idle["idle_gaps"])
    listed = _script("idle_by_span").window_spans(spans, kept["epoch_ns"], 2.0)
    assert listed["per_name"]["read"][0] >= 1 and listed["view_splice"]


def _window_end(kept):
    """Seconds from the window's start to the last span the ring kept."""
    last = max(s.start_ns + s.dur_ns for s in kept["spans"])
    return (last - kept["epoch_ns"]) / 1e9


def _script(name):
    import importlib.util

    path = Spec().root / "scripts" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"scripts_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
