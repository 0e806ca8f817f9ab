"""Find an open-loop cell's knee: serve its mix at each of a few fixed
rates, in one process, and print how the queue and the tails behave.

    python3 benchmarks/chip/sweep.py --workload olmo-1b.chat_open \\
        --seconds 20 --rates 4 6 8 10 12

The knee is the highest rate at which the queue for a slot does not
grow over the window; the cell then runs at about 0.8 of it, the rate
written in its mix. Run once when a cell is defined, not by the
benchmark's runs. One JSON line per rate.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell, cfg, mix, limits = harness.load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    t = T_START
    for rate in args.rates:
        res = harness.run(cell, cfg, dict(mix, rate=rate), limits,
                          seed=args.seed, seconds=args.seconds, trace=False,
                          t_start=t, peak=None)
        rec = res["rec"]
        row = {"rate": rate, "offered": len(rec.arrivals),
               "queue_growth": rec.counters["queued"],
               "finished": sum(1 for _d, _s, h in rec.arrivals
                               if h.finished),
               "preemptions": rec.counters["preemptions"],
               "correct": res["correct"], "setup_s": rec.setup_s,
               "check": res["checks"]}
        for name in ("ttft_p95_ms", "itl_p95_ms", "tokens_per_s"):
            row[name] = harness.reader(name)(rec)
        print(json.dumps(row), flush=True)
        t = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
