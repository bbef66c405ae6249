"""Run one benchmark cell on the chip and print its result.

    python3 benchmarks/chip/run.py --workload danube2.chat --seed 7 \
        --seconds 20 --trace 0

Everything is found by the names in ``BENCHMARK.json``: the cell, its
configuration (``configs/``), its traffic mix (``traffic/``) and the reader
of each metric (``metrics/``).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics, with the profiler on over the whole
window.  Every run ends by comparing answers the window
produced with the plain float32 reference; each number compared is printed
beside its limit as the last lines on standard error and under ``checks``,
the last key of the result.  The last line on standard output is the result.

Without a TPU, with fewer chips than the cell asks for, or on a chip kind
missing from the peak table, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import manifest, runner
    sys.path.insert(0, str(manifest.ROOT / "src"))
    try:
        result, checks = runner.run(args.workload, args.seed, args.seconds,
                                    bool(args.trace), T_START)
    except runner.harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    for line in checks:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
