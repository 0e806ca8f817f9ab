"""Readings for a cell's limits: the program's widest logit gap and the
control's, seed by seed, in one process.

    python3 benchmarks/chip/control.py --workload yi-6b.decode_heavy \\
        --seconds 30 --seeds 11 12 13

Each seed is a whole run of the cell (set-up, window, comparison). On
the same sampled requests it reads the gap of the served tokens and
puts the control, the reference computed in int8
(``configs/dense_decoder.forward(quant=True)``), in the program's
place: the gap of the tokens it puts first, judged by the same
comparison (``check.verdict``) with the cell's limits. A limit goes
between the largest program reading and the smallest control reading;
``limits/<workload>.json`` records both. The benchmark's own runs do
not run the control. Prints one JSON line per seed, then a summary, and
exits with 1 where the control came out correct on any seed (the limit
then fails to tell the control from the program).
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[0] = str(HERE.parent)
sys.path.insert(1, str(HERE.parents[1] / "src"))

from chip import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell, cfg, mix, limits = harness.load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    rows = []
    t = T_START
    for seed in args.seeds:
        res = harness.run(cell, cfg, mix, limits, seed=seed,
                          seconds=args.seconds, trace=False, t_start=t,
                          peak=None, control=True)
        row = {"seed": seed,
               "program_gap": res["checks"]["logit_gap"]["value"],
               "control_gap": res["control_checks"]["logit_gap"]["value"],
               "tokens": res["checks"]["tokens_compared"]["value"],
               "correct": res["correct"],
               "control_correct": res["control_correct"],
               "control_check": res["control_checks"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del res
        gc.collect()
        t = time.monotonic()
    print(json.dumps({
        "workload": args.workload,
        "program_gap_max": max(r["program_gap"] for r in rows),
        "control_gap_min": min(r["control_gap"] for r in rows),
        "program_correct": sum(r["correct"] for r in rows),
        "control_correct": sum(r["control_correct"] for r in rows),
        "runs": len(rows)}))
    return 1 if any(r["control_correct"] for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
