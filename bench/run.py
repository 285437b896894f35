"""Serving benchmark: one run of one cell of ``BENCHMARK.json`` on the chip.

    python bench/run.py --workload granite.decode_long --seed 7 \
        --seconds 30 --trace 0

Set-up (weights made on the device from the seed, every program the cell
uses built and warmed, the cell's traffic served until the slots are busy)
is timed from process start; then the cell's traffic is served for
``--seconds`` and its end-to-end metrics (``--trace 0``) or per-layer
metrics (``--trace 1``, read from a profile of a stretch served after the
window) are printed.  Then the served tokens of a sample of finished
requests are replayed through the configuration's plain reference, which
reads how far each served token's logit lies below the reference's best;
the statistics of those gaps that ``bench/limits/<workload>.json`` names,
each held to its limit, decide ``correct``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``check``: each compared number with its limit).
The run refuses anything but a TPU with enough chips: it exits non-zero and
prints no result there.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        import repro  # noqa: F401  -- the system under test
    except (harness.BenchError, ImportError, KeyError, OSError) as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr)
        return 2
    try:
        result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START)
    except harness.BenchError as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
