"""Paths and the tiny benchmark tree the benchmark's CPU tests share.

Importing it puts ``src`` (the program) and ``bench`` (the harness) on the
path.  ``make_tree`` builds a throwaway checkout whose cells are tiny, so a
test can run one end to end on the CPU with the look for a chip skipped.
"""
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
for p in (str(REPO / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny cells: (cell, configuration, reference it borrows, traffic)
TINY_CELLS = [
    ("tiny-granite.closed", "tiny-granite", "granite-moe-3b-a800m",
     "tiny-closed"),
    ("tiny-mamba.closed", "tiny-mamba", "mamba2-1.3b", "tiny-closed"),
    ("tiny-granite.open", "tiny-granite", "granite-moe-3b-a800m",
     "tiny-open"),
    ("tiny-mamba.open", "tiny-mamba", "mamba2-1.3b", "tiny-open"),
]


def make_tree(root: Path) -> Path:
    """A checkout-like tree at ``root`` whose ``BENCHMARK.json`` holds the
    tiny cells, with the real metrics and the real references."""
    bench = root / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    configs, cells = {}, []
    for cell, config, ref, traffic in TINY_CELLS:
        shutil.copy(DATA / f"{config}.json", bench / "configs")
        shutil.copy(BENCH / "configs" / f"{ref}.py",
                    bench / "configs" / f"{config}.py")
        shutil.copy(DATA / f"{traffic}.json", bench / "traffic")
        configs[config] = {"name": config, "source": "tiny",
                           "file": f"bench/configs/{config}.json",
                           "reduced": [], "why": "tiny"}
        cells.append({"name": cell, "config": config, "traffic": traffic,
                      "chips": 1, "why": "tiny"})
    # every metric the harness can read, in every tiny cell
    e2e = [{"name": n, "unit": u, "better": b, "bound": 0.25,
            "source": "host_clock"}
           for n, u, b in [("gen_tokens_per_s", "tokens/s", "higher"),
                           ("request_p95_s", "s", "lower"),
                           ("setup_s", "s", "lower")]]
    per_layer = [{"name": f.stem, "unit": "x", "better": "lower",
                  "source": "host_clock", "layer": "any",
                  "moves": "setup_s"}
                 for f in sorted((BENCH / "metrics").glob("*.py"))]
    spec = dict(real, configs=list(configs.values()), workloads=cells,
                end_to_end=e2e, per_layer=per_layer)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench
