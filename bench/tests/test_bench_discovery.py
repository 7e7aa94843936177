"""A new traffic mix and a new per-layer metric are found by name from
BENCHMARK.json, with no existing file edited."""
import json
import shutil

from bench_testlib import DATA
from harness import spec


def test_new_traffic_and_metric_found_by_name(tiny_tree):
    root, bench = tiny_tree
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    # what a later PR adds: a traffic file, a metric reader, and entries
    shutil.copy(DATA / "tiny-open.json", bench / "traffic" / "new-mix.json")
    (bench / "metrics" / "new_metric.ms").with_suffix(".ms.py").write_text(
        "def read(run):\n    return 42.0\n")
    bj = json.loads((root / "BENCHMARK.json").read_text())
    bj["workloads"].append({"name": "tiny-granite.new", "config": "tiny-granite",
                            "traffic": "new-mix", "chips": 1, "why": "new"})
    bj["per_layer"].append({"name": "new_metric.ms", "unit": "ms",
                            "better": "lower", "source": "host_clock",
                            "layer": "new", "moves": "request_p95_s",
                            "workloads": ["tiny-granite.new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bj))

    cell = spec.load_cell(root, "tiny-granite.new", bench)
    assert cell.traffic == json.loads((DATA / "tiny-open.json").read_text())
    assert "new_metric.ms" in [m["name"] for m in cell.per_layer]
    assert cell.metric_reader("new_metric.ms").read(None) == 42.0
    assert hasattr(cell.reference(), "reference_logits")
    # the old cells do not see the new cell's metric
    old = spec.load_cell(root, "tiny-granite.closed", bench)
    assert "new_metric.ms" not in [m["name"] for m in old.per_layer]
    # nothing that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())


def test_real_benchmark_files_resolve():
    """Every cell of the repository's BENCHMARK.json finds its files."""
    from bench_testlib import REPO
    bj = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bj["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        assert cell.config["arch"]["name"] == w["config"]
        assert hasattr(cell.reference(), "reference_logits")
        for m in cell.per_layer:
            assert callable(cell.metric_reader(m["name"]).read)
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
