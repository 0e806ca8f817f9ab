"""The benchmark's entry point: one run of one cell, on the chips of the
machine it is started on.

    python3 benchmarks/chip/run.py --workload yi-6b.decode_heavy \\
        --seed 7 --seconds 30 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``check``, each number compared
beside its limit. The same numbers end standard error. Without a TPU,
or with fewer chips than the cell asks for, it prints no result and
exits with 3.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# the benchmark imports as the package ``chip``, the program from src/
sys.path[0] = str(HERE.parent)
sys.path.insert(1, str(ROOT / "src"))

from chip import devtrace, harness  # noqa: E402


def compile_events(log: list):
    """Record the time of every program JAX compiled or loaded from the
    persistent cache: JAX times both as a backend compile and marks the
    loads as cache hits."""
    import jax

    def on_duration(event, _duration, **_kw):
        if event.endswith("backend_compile_duration"):
            log.append(("compile", time.monotonic()))

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            log.append(("load", time.monotonic()))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def host_side(rec) -> str:
    """The host's side of the window: its slowest engine steps, each with
    the prompt tokens it prefilled and what the process did meanwhile
    (CPU seconds over all threads, involuntary context switches, major
    page faults), the collector's pauses, and the spread of the token
    gaps. A stall that no tail shows would be here."""
    rows = []
    for i, s in enumerate(rec.steps):
        p = rec.steps[i - 1] if i else s
        rows.append((s[1] - s[0], s[0] - rec.t0, s[2] - p[2], s[3] - p[3],
                     s[4] - p[4], s[5] - p[5]))
    slow = sorted(rows, key=lambda r: -r[0])[:5]
    gaps = sorted(b - a for h in rec.handles
                  for a, b in zip(h.t_tokens, h.t_tokens[1:])
                  if rec.t0 < b <= rec.t1)
    q = {k: 1e3 * gaps[max(0, -(-k * len(gaps) // 100) - 1)]
         for k in (50, 95, 99)} if gaps else {}
    return (f"window: {len(rec.steps)} steps, slowest (ms at s: prompt "
            "tokens prefilled, cpu ms, involuntary switches, major faults): "
            + ", ".join(f"{1e3 * r[0]:.1f} at {r[1]:.2f}: {r[2]}, "
                        f"{1e3 * r[3]:.0f}, {r[4]}, {r[5]}" for r in slow)
            + f"; gc: {len(rec.gc_pauses)} pauses, "
            f"{1e3 * sum(rec.gc_pauses):.1f} ms in all, "
            f"{1e3 * max(rec.gc_pauses, default=0):.1f} ms at most; "
            f"preemptions: {rec.counters['preemptions']}; token gaps ms "
            + " ".join(f"p{k} {v:.2f}" for k, v in q.items()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, cfg, mix, limits = harness.load_cell(args.workload, bench)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX sees "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    peaks = harness.load_json("peaks.json")["devices"]
    if devs[0].device_kind not in peaks:
        print(f"no peaks for device kind {devs[0].device_kind!r} in "
              "peaks.json", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    compiles = []
    compile_events(compiles)

    res = harness.run(cell, cfg, mix, limits, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      t_start=T_START, peak=peaks[devs[0].device_kind])
    rec = res["rec"]
    metrics = {}
    for name, unit in harness.wanted_metrics(bench, args.workload,
                                             per_layer=bool(args.trace)):
        value = harness.reader(name)(rec)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(m.get("peak_bytes_in_use", 0)
                                       for m in res["mem"])}
    out = {"correct": res["correct"],
           "attempted": len(rec.arrivals) if rec.loop == "open"
           else len(rec.handles),
           "failed": res["failed"], "metrics": metrics, "device": device}
    if rec.trace is not None:
        device["busy_s"] = devtrace.busy_s(rec.trace)
        lo, hi = rec.trace.window()
        device["window_s"] = hi - lo
        out["breakdown"] = {"device_ops": devtrace.top_ops(rec.trace),
                            "idle_gaps": devtrace.idle_gaps(rec.trace)}
    # where the run's time went, phase by phase, with the compilations in
    # each (the window must have none)
    phases, prev = [], None
    for name, t in res["stamps"].items():
        if prev is not None:
            n = [k for k, c in compiles if prev < c <= t]
            loads = n.count("load")
            phases.append(f"{name} {t - prev:.2f} s ({len(n) - 2 * loads}"
                          f" compiled, {loads} loaded)")
        prev = t
    print("phases: " + ", ".join(phases), file=sys.stderr)
    print(host_side(rec), file=sys.stderr)
    for name, c in res["checks"].items():
        bound = f"<= {c['max']}" if "max" in c else f">= {c['min']}"
        print(f"check {name}: {c['value']} {bound}", file=sys.stderr)
    out["check"] = res["checks"]
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
