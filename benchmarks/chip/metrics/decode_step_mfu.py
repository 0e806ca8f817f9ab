"""decode_step_mfu (whole step): model operations of the window's decode
steps over their device time times the chip's peak rate."""

from chip import counts, programs


def read(run):
    got = programs.module_time(run, programs.DECODE_STEP)
    if not got or not got[0]:
        return None
    flops = counts.decode_step_flops(run.dm, *programs.decode_work(run))
    return counts.mfu(flops, got[0], run.peak)
