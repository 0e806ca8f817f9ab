"""queue_wait_p95_ms (scheduler): 95th percentile, over the requests due
in the window, of the time each waited in the engine's queue for a
slot: its ``t_admitted`` stamp less its ``t_submit``. A request not
admitted when the window closes counts the time it has waited. None
for a program whose handles carry no ``t_admitted``."""

from chip import pctl


def read(run):
    if run.loop != "open" or not run.arrivals \
            or not hasattr(run.arrivals[0][2], "t_admitted"):
        return None
    waits = [min(run.t1 if h.t_admitted is None else h.t_admitted, run.t1)
             - h.t_submit for _due, _sub, h in run.arrivals]
    return 1e3 * pctl.percentile(waits, 95)
