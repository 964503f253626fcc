"""CPU rehearsals of the g500-22.pagerank cell through the harness."""

from bench.tests.conftest import CONTRACT_KEYS, TRACED_KEYS, expected_metrics


def test_pagerank_rehearsal(rehearse):
    r = rehearse("g500-22.pagerank")
    assert list(r) == CONTRACT_KEYS
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == expected_metrics("g500-22.pagerank", False, r)


def test_pagerank_traced_rehearsal(rehearse):
    r = rehearse("g500-22.pagerank", trace=True)
    assert list(r) == TRACED_KEYS
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == expected_metrics("g500-22.pagerank", True, r)
    assert 0 < r["metrics"]["pagerank_roofline"]["value"] <= 100
    assert r["metrics"]["compiles.pagerank"]["value"] >= 1
