"""submit_lag_p95_ms (load generator): 95th percentile of how late each
window request was handed to the engine after it was due. The generator
is one loop that submits between engine steps, so this is about one
step; a larger value means the generator, not the server, was slow."""

from chip import pctl


def read(run):
    if run.loop != "open" or not run.arrivals:
        return None
    return 1e3 * pctl.percentile([s - d for d, s, _h in run.arrivals], 95)
