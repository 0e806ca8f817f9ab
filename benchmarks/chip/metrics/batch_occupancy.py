"""batch_occupancy (scheduler): decoding slots over slots offered, summed
over the window's decode steps, from the scheduler's own counters."""


def read(run):
    c = run.counters
    if not c["steps"]:
        return None
    return c["slot_steps"] / (c["steps"] * run.mix["slots"])
