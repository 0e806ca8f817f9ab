"""itl_p95_ms: 95th percentile of every gap between consecutive tokens
of every request, where the later token falls inside the window.

All the slots of a decode step get their token together, so the gaps
come in groups of one step's width; in an open loop the slowest steps
are those that carry a prefill."""

from chip import pctl


def read(run):
    gaps = [b - a for h in run.handles
            for a, b in zip(h.t_tokens, h.t_tokens[1:])
            if run.t0 < b <= run.t1]
    return 1e3 * pctl.percentile(gaps, 95) if gaps else None
