"""paged_decode_roofline (kernels): the least time the paged decode
kernel's work of the window needs at the chip's peaks (operations and
bytes from the slots' context lengths) over the kernel's device time."""

from chip import counts, programs


def read(run):
    t = programs.kernel_time(run, programs.DECODE_STEP)
    if not t:
        return None
    live, slots = programs.decode_work(run)
    flops, nbytes = counts.paged_decode(run.dm, live, slots)
    return counts.roofline_share(flops, nbytes, t, run.peak)
