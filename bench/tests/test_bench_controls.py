"""Each cell's control at the test size: PageRank in bfloat16, an
acknowledged write missing an op.  The rest of
the run goes on, and ``correct`` comes out false."""

import pytest


@pytest.mark.parametrize("cell,check", [
    ("g500-22.pagerank", "pagerank_max_rel_err"),
    ("uniform-21.ingest", "readback_deleted_present"),
])
def test_control_is_incorrect(rehearse, cell, check):
    r = rehearse(cell, control=True)
    assert r["correct"] is False
    n, limit = r["checks"][check]
    assert n > limit
