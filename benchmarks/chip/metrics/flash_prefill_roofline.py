"""flash_prefill_roofline (kernels): the least time the causal prefill
attention of the window's prompts needs at the chip's peaks over the
flash kernel's device time."""

from chip import counts, programs


def read(run):
    t = programs.kernel_time(run, programs.PREFILL_STEP)
    if not t or not run.prefilled:
        return None
    flops, nbytes = counts.flash_prefill(run.dm, run.prefilled)
    return counts.roofline_share(flops, nbytes, t, run.peak)
