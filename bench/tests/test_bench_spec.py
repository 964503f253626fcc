"""Cells, configurations, traffic mixes and metrics are found by name: a
later change adds them as files and entries, with no edit to a file that
is there.  And the repository's own BENCHMARK.json is whole."""

import json
import shutil

import numpy as np

from bench.harness.cell import Run
from bench.harness.spec import ROOT, Spec


def test_new_pieces_found_by_name(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__", "tests"))
    (tmp_path / "bench/configs/tiny-8.json").write_text(json.dumps({
        "graph": {"generator": "uniform", "scale": 8, "edgefactor": 4, "permute": False}}))
    (tmp_path / "bench/traffic/trickle.json").write_text(json.dumps({
        "reads": {"op": "search", "rate_per_s": 5.0}}))
    (tmp_path / "bench/metrics/answered_share.py").write_text(
        "import numpy as np\n\n\n"
        "def read(run):\n"
        "    return 100.0 * float(np.mean(~np.isnan(run.answered)))\n")
    bench["configs"].append({"name": "tiny-8", "source": "a test", "reduced": [],
                             "file": "bench/configs/tiny-8.json", "why": "a test"})
    bench["workloads"].append({"name": "tiny-8.trickle", "config": "tiny-8",
                               "traffic": "trickle", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "answered_share", "unit": "%", "better": "higher",
                               "source": "host_clock", "layer": "query path",
                               "moves": "ingest_ops_per_s", "workloads": ["tiny-8.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = Spec(tmp_path)
    cell = spec.cell("tiny-8.trickle")
    assert spec.config(cell["config"])["graph"]["scale"] == 8
    assert spec.traffic(cell["traffic"])["reads"]["rate_per_s"] == 5.0
    names = [m["name"] for m in spec.metrics("tiny-8.trickle", True)]
    assert names == ["answered_share"]
    run = Run(cell="tiny-8.trickle", config={}, traffic={}, seconds=1.0)
    run.answered = np.array([0.1, np.nan, 0.2, 0.3])
    assert spec.reader("answered_share")(run) == 75.0
    # the pieces that were there are found as before
    assert [m["name"] for m in spec.metrics("uniform-21.ingest", False)] == \
        [m["name"] for m in Spec().metrics("uniform-21.ingest", False)]


def test_benchmark_is_whole():
    spec = Spec()
    b = spec.bench
    cells = {w["name"] for w in b["workloads"]}
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(b["paths"][0] + "/")
        assert any(w["config"] == c["name"] for w in b["workloads"])
    for w in b["workloads"]:
        assert (spec.dir / "traffic" / f"{w['traffic']}.json").is_file()
        e2e = [m["name"] for m in spec.metrics(w["name"], False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.metrics(w["name"], True)
    for m in b["end_to_end"] + b["per_layer"]:
        assert (spec.dir / "metrics" / f"{m['name']}.py").is_file(), m["name"]
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in moved.get("workloads", cells), (m["name"], c)
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_run_without_a_tpu_exits_nonzero():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench/run.py"), "--workload",
                        "uniform-21.ingest", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
