"""setup_s: process start to window start (build, weights from the seed,
warm-up of every shape the cell uses, and the cell's own lead-in or
equilibrium fill), on the host's clock."""


def read(run):
    return run.setup_s
