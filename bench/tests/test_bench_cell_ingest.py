"""CPU rehearsals of the uniform-21.ingest cell through the harness."""

from bench.tests.conftest import CONTRACT_KEYS, TRACED_KEYS, expected_metrics


def test_ingest_rehearsal(rehearse):
    r = rehearse("uniform-21.ingest")
    assert list(r) == CONTRACT_KEYS
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == expected_metrics("uniform-21.ingest", False, r)
    assert r["metrics"]["ingest_ops_per_s"]["value"] > 0


def test_ingest_traced_rehearsal(rehearse):
    r = rehearse("uniform-21.ingest", trace=True)
    assert list(r) == TRACED_KEYS
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == expected_metrics("uniform-21.ingest", True, r)
    assert 0 < r["metrics"]["wal_sync_share.ingest"]["value"] < 100
