"""What the per-layer readers look for in a run: the program's compiled
steps and kernels by the names they run under on the device, and the
sums over them.

The engine's steps are jitted functions, and a compiled program runs
under its function's name. A Pallas kernel runs as a ``tpu_custom_call``
op whose trace name does not carry the kernel's own name, so a kernel is
found as the custom call inside the one step program that calls it: the
paged decode kernel in the decode step, the flash kernel in the prefill.
These names are the program's; a change that renames one leaves its
metric silent, which a ``--trace 1`` run of a cell that lists the metric
reports as missing.
"""

from __future__ import annotations

from chip import devtrace

DECODE_STEP = "overlap_fn"       # fused feed + decode + on-device sampler
PREFILL_STEP = "prefill_fn"      # batched bucketed prefill + pool pack
KERNEL = "tpu_custom_call"       # a Pallas kernel, as the trace names it


def module_time(run, word: str):
    """(device seconds, count) of the compiled steps named ``word`` that
    ran in the traced window; None without a trace."""
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    return devtrace.time_of(run.trace.modules, word, lo, hi)


def kernel_time(run, step: str):
    """Device seconds of the Pallas kernels inside the step program named
    ``step`` in the traced window; None without a trace."""
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    return devtrace.within(run.trace, step, KERNEL, lo, hi)


def decode_work(run):
    """(live token steps, slot steps) of the window's decode steps."""
    c = run.counters
    return c["live_token_steps"], c["slot_steps"]
