"""prefill_step_ms (model step): device time of the prefill programs per
request prefilled in the window, from the trace."""

from chip import programs


def read(run):
    got = programs.module_time(run, programs.PREFILL_STEP)
    if not got or not got[1] or not run.prefilled:
        return None
    return 1e3 * got[0] / len(run.prefilled)
