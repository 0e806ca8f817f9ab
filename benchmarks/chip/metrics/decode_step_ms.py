"""decode_step_ms (model step): device time of the decode step program
per step, from the trace."""

from chip import programs


def read(run):
    got = programs.module_time(run, programs.DECODE_STEP)
    if not got or not got[1]:
        return None
    return 1e3 * got[0] / got[1]
