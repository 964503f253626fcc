"""Shared helpers for the benchmark's tests: CPU rehearsals of a cell at a
size a test run can hold."""

import pytest

from bench.harness import spec as spec_mod

# scale 10 (1,024 vertices), a 2 s window; writes and reads as the cell's
# traffic file says, with the stream sized for the short window
SMALL = {"config": {"graph": {"scale": 10}},
         "traffic": {"writes": {"overrun_s": 2, "max_ops_per_s": 16000}}}
SEED = 2**33 + 12345  # above 32 bits, as the driver's seeds are


@pytest.fixture
def rehearse(tmp_path, monkeypatch):
    """Run a cell through the harness on the CPU: no chip check, no
    persistent compile cache, WAL and trace under ``tmp_path``.  The CPU
    has no row in the peaks table, so the chip's peaks stand in for the
    roofline arithmetic."""
    from bench.harness.main import run_cell

    monkeypatch.setattr(spec_mod.Spec, "peaks",
                        lambda self, kind: spec_mod.peaks(self.dir / "peaks.json", "TPU v5 lite"))

    def go(cell, trace=False, seconds=2.0, seed=SEED, **kw):
        return run_cell(cell, seed, seconds, trace, require_chip=False, overrides=SMALL,
                        out_dir=tmp_path, compile_cache=False, **kw)

    return go


CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
TRACED_KEYS = ["correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"]


def expected_metrics(cell, traced, result):
    """The cell's metrics of that kind; on the CPU, which reports no device
    memory, without the one read from it."""
    from bench.harness.spec import Spec

    want = {m["name"] for m in Spec().metrics(cell, traced)}
    if result["device"]["memory_peak_bytes"] is None:
        want.discard("device_bytes_per_edge")
    return want
