"""Planted faults: with what the store produces broken underneath, the
rest of a run goes on and ``correct`` comes out false.  One test per fault
each cell can have: an answer altered where it is produced, half of a
batch left out, an acknowledged write missing ops, a read answered from a
view behind its stamp."""

import pytest

from bench.harness.control import Fault


@pytest.mark.parametrize("cell,fault,check", [
    ("uniform-21.ingest", "flip_one", "wrong_answers"),
    ("uniform-21.ingest", "half_batch", "wrong_answers"),
    ("g500-22.pagerank", "alter_rank", "pagerank_max_rel_err"),
    ("uniform-21.ingest", "drop_delete", "readback_deleted_present"),
    ("uniform-21.ingest", "half_write", "readback_missing"),
    ("uniform-21.ingest", "stale_view", "wrong_answers"),
    ("g500-22.pagerank", "stale_view", "pagerank_max_rel_err"),
])
def test_fault_makes_run_incorrect(rehearse, cell, fault, check):
    f = Fault(fault)
    r = rehearse(cell, fault=f)
    assert f.fired > 0
    assert r["correct"] is False
    n, limit = r["checks"][check]
    assert n > limit
