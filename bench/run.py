"""Run one cell of the chip benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for; anything else exits non-zero before measuring.  Everything
a cell needs is found by name from ``BENCHMARK.json``: its configuration
and plain reference under ``bench/configs/``, its traffic mix under
``bench/traffic/``, its per-layer metrics' readers under
``bench/metrics/``.  The last line of standard output is one JSON object;
the numbers that decide ``correct`` are also the last lines of standard
error.

``--control 1``, for calibration only and never in the benchmark's own
runs, puts the fp8 control in the program's place in the comparison, which
a sound limit then fails (``correct`` false).
"""
import time

T_PROCESS = time.monotonic()   # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path.cwd()
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
# the compile cache lives at a fixed path inside the checkout, whatever the
# environment names; the program takes the directory from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from harness import cell
    return cell.run(ROOT, args, T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
