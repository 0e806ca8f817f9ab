"""prefill_step_mfu (whole step): model operations of the window's
prefills over the prefill programs' device time times the peak rate."""

from chip import counts, programs


def read(run):
    got = programs.module_time(run, programs.PREFILL_STEP)
    if not got or not got[0] or not run.prefilled:
        return None
    flops = counts.prefill_flops(run.dm, run.prefilled)
    return counts.mfu(flops, got[0], run.peak)
