"""device_idle_frac.closed (device): share of the window in which no
operation ran on the device, from the trace."""

from chip import devtrace


def read(run):
    if run.trace is None or not run.trace.devices or run.loop != "closed":
        return None
    lo, hi = run.trace.window()
    return 1.0 - devtrace.busy_s(run.trace) / (hi - lo)
