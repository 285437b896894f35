"""Readings that set a cell's correctness limits, on the chip.

    python bench/control.py --workload granite.decode_long \
        --seeds 101 102 103 --seconds 8 --out control.json [--witness]

For each seed, one process serves the cell as ``bench/run.py`` does (a
short window at the cell's own load), replays a sample of the finished
requests through the plain reference, and reads, per sampled request, the
gap by which each served token's logit lies below the reference's best:

* the program's gaps, judged against ``bench/limits/<workload>.json``
  (``correct``);
* the control's: the same gaps for the tokens that the reference computed
  one precision lower (float8 weights) puts first, put in the program's
  place and judged by the same comparison (``control_correct``, which has
  to come out false);
* with ``--witness``, the gaps of the tokens that the reference computed
  one precision higher (float32 activations) puts first: how far rounding
  alone moves the model's tokens.

Each compared number is printed per seed for all three, so the limit can
be set between the largest sound reading and the smallest control reading.
The benchmark's own runs never run the control or the witness.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--witness", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np
    from bench import harness
    cell = harness.load_cell(args.workload)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, t_start=t0, control=True,
                               witness=args.witness)
        r = res.get("readings") or {}
        if "gaps" not in r:
            print(f"[control] seed {seed}: no request finished; no reading",
                  flush=True)
            continue
        kinds = ["program", "control"] + (["witness"] if args.witness else [])
        gaps = {"program": r["gaps"], "control": r["control_gaps"],
                "witness": r.get("witness_gaps")}
        row = {"seed": seed, "correct": res["correct"],
               "control_correct": r["control_correct"],
               "control_weight_rel_err": r["control_weight_rel_err"],
               "requests": len(r["gaps"]),
               "tokens": [int(g.size) for g in r["gaps"]],
               "stats": {k: harness.gap_stats(gaps[k]) for k in kinds},
               "setup_s": res["metrics"].get("setup_s", {}).get("value"),
               "run_s": time.perf_counter() - t0}
        rows.append(row)
        np.savez_compressed(
            os.path.splitext(args.out)[0] + f"_{seed}.npz",
            lengths=np.array(row["tokens"]),
            **{k: np.concatenate(gaps[k]) for k in kinds})
        print(json.dumps(row), flush=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    if not rows:
        return 1
    for name in cell.limits:
        prog = max(r["stats"]["program"][name] for r in rows)
        ctrl = min(r["stats"]["control"][name] for r in rows)
        print(f"[control] {args.workload} {name}: program largest {prog} "
              f"over {len(rows)} seeds, control smallest {ctrl}; limit "
              f"{cell.limits[name]['limit']}; program correct on "
              f"{sum(r['correct'] for r in rows)}, control correct on "
              f"{sum(r['control_correct'] for r in rows)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
