"""tokens_per_s: output tokens stamped inside the window over the
window's length. The window opens and closes at the end of an engine
step, so it holds whole steps and every token they made."""


def read(run):
    n = sum(1 for h in run.handles for t in h.t_tokens
            if run.t0 < t <= run.t1)
    return n / (run.t1 - run.t0)
