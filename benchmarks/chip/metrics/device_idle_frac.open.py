"""device_idle_frac.open (device): share of the time the engine held a
request in which no operation ran on the device. The load generator
sleeps only when the engine holds none, so that time is the window
less its ``wait_arrival`` spans."""

from chip import devtrace


def read(run):
    if run.trace is None or not run.trace.devices or run.loop != "open":
        return None
    tr = run.trace
    lo, hi = tr.window()
    waits = devtrace.union(devtrace.clip(
        [s for s in tr.spans if s.name == "wait_arrival"], lo, hi))
    held = hi - lo - devtrace.length(waits)
    if held <= 0:
        return None
    idle = []
    for d in range(tr.devices):
        b = devtrace.busy(tr, d, lo, hi)
        busy_held = devtrace.length(b) - devtrace.overlap(b, waits)
        idle.append(1.0 - busy_held / held)
    return sum(idle) / len(idle)
