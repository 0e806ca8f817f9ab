"""ttft_p95_ms: 95th percentile, over every request due inside the
window, of first-token time minus due time. A request without a first
token when the window closes counts with the time it has waited, so a
stall cannot drop out of the tail."""

from chip import pctl


def read(run):
    if run.loop != "open":
        return None
    waits = [(h.t_first_token if h.t_first_token is not None else run.t1)
             - due for due, _sub, h in run.arrivals]
    return 1e3 * pctl.percentile(waits, 95) if waits else None
