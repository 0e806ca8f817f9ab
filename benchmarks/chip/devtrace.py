"""The profiler's trace of a window, reduced to what the per-layer
metrics read.

``capture()`` records a trace with JAX's profiler into a temporary
directory and reads it back with ``jax.profiler.ProfileData``; nothing
of it stays on disk. ``Trace`` keeps, on one clock in seconds:

* ``ops``: device operations (the ``XLA Ops`` line of each TPU plane),
* ``modules``: compiled programs run on the device (``XLA Modules``),
* ``spans``: the benchmark's own host spans (``TraceAnnotation``), which
  mark the measured window and the calls into the engine.

The reductions below work on that form alone, so the tests feed them
small hand-made traces.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import os
import re
import tempfile

WINDOW_SPAN = "window"
# Ops that only hold others (a loop over the layers, a branch, a call):
# their children are the device's work.
CONTAINERS = ("while", "conditional", "call")
_HLO = re.compile(r"%(?P<inst>[^ ]+) = ")


@dataclasses.dataclass
class Event:
    name: str
    start: float                 # seconds on the trace's clock
    end: float
    device: int = -1             # TPU plane index, -1 for host spans
    meta: str = ""               # the event's string stats, joined

    def has(self, word: str) -> bool:
        return word in self.name or word in self.meta


@dataclasses.dataclass
class Trace:
    ops: list[Event]
    modules: list[Event]
    spans: list[Event]
    devices: int

    def window(self) -> tuple[float, float]:
        """The measured window, from the benchmark's ``window`` span."""
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span, "
                             f"found {len(w)}")
        return w[0].start, w[0].end


@contextlib.contextmanager
def capture(out: list):
    """Trace the body; on exit append the ``Trace`` to ``out``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one xplane file, found {paths}")
        out.append(load(paths[0]))


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a ``Trace``. Every host event on the
    thread that carries the ``window`` span becomes a span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], collections.defaultdict(list)
    dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                kind = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if kind is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    meta = " ".join(v for _k, v in e.stats
                                    if isinstance(v, str))
                    kind.append(Event(e.name, s, s + e.duration_ns * 1e-9,
                                      dev, meta))
            dev += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    host[(plane.name, line.name)].append(
                        Event(e.name, s, s + e.duration_ns * 1e-9))
    spans = []
    for events in host.values():
        if any(e.name == WINDOW_SPAN for e in events):
            spans += events
    return Trace(ops=ops, modules=modules, spans=spans, devices=dev)


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------


def clip(events, lo: float, hi: float):
    """(start, end) of each event cut to [lo, hi]; empty ones dropped."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, sorted."""
    merged = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], t))
        else:
            merged.append((s, t))
    return merged


def length(intervals) -> float:
    return sum(t - s for s, t in intervals)


def overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def busy(trace: Trace, device: int, lo: float, hi: float):
    """Disjoint intervals of [lo, hi] in which an operation ran on
    ``device``."""
    return union(clip([e for e in trace.ops if e.device == device], lo, hi))


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which an operation ran, averaged over
    the devices."""
    lo, hi = trace.window()
    return sum(length(busy(trace, d, lo, hi))
               for d in range(trace.devices)) / max(trace.devices, 1)


def time_of(events, word: str, lo: float, hi: float) -> tuple[float, int]:
    """(seconds inside [lo, hi], count) of the events whose name or stats
    hold ``word``, counting an event when it starts in the window."""
    sel = [e for e in events if e.has(word)]
    n = sum(1 for e in sel if lo <= e.start < hi)
    return length(clip(sel, lo, hi)), n


def within(trace: Trace, module_word: str, op_word: str, lo: float,
           hi: float) -> float:
    """Seconds inside [lo, hi] of the operations named ``op_word`` that
    start inside a run of a compiled program named ``module_word`` on the
    same device."""
    runs = collections.defaultdict(list)
    for m in trace.modules:
        if m.has(module_word):
            runs[m.device].append((m.start, m.end))
    starts = {d: [r[0] for r in sorted(v)] for d, v in runs.items()}
    runs = {d: sorted(v) for d, v in runs.items()}
    sel = []
    for e in trace.ops:
        if e.device not in runs or not e.has(op_word):
            continue
        i = bisect.bisect_right(starts[e.device], e.start) - 1
        if i >= 0 and e.start < runs[e.device][i][1]:
            sel.append(e)
    return length(clip(sel, lo, hi))


def split_hlo(name: str):
    """(instruction, kind, result shape) of an op named by its HLO text,
    such as ``%fusion.3 = bf16[8,128]{1,0} fusion(...)``; the name itself
    and empty strings when it is not HLO text."""
    m = _HLO.match(name)
    if not m:
        return name, "", ""
    rest = name[m.end():]
    depth, i = 0, 0
    for i, ch in enumerate(rest):      # the result shape may be a tuple
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0 and ch == " ":
            break
    shape, tail = rest[:i], rest[i + 1:]
    kind = tail[:tail.find("(")] if "(" in tail else tail
    return m["inst"], kind, re.sub(r"\{[^{}]*\}", "", shape)


def short_name(name: str) -> str:
    inst, kind, shape = split_hlo(name)
    return " ".join(x for x in (inst, kind, shape[:60]) if x)


def top_ops(trace: Trace, k: int = 10):
    """The ``k`` device operations that took most time in the window, by
    name, as [name, seconds] (summed over devices and over the
    iterations of a loop; ops that only hold others are left out)."""
    lo, hi = trace.window()
    tot = collections.Counter()
    for e in trace.ops:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s and split_hlo(e.name)[1] not in CONTAINERS:
            tot[short_name(e.name)] += t - s
    return [[n, s] for n, s in tot.most_common(k)]


def idle_gaps(trace: Trace, k: int = 10, device: int = 0):
    """The ``k`` longest stretches of the window with no operation on
    ``device``, each named by the innermost host span that covers its
    middle ("host" where none does), as [name, seconds]."""
    lo, hi = trace.window()
    gaps, t = [], lo
    for s, e in busy(trace, device, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    spans = [s for s in trace.spans if s.name != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        cover = [x for x in spans if x.start <= mid <= x.end]
        name = min(cover, key=lambda x: x.end - x.start).name \
            if cover else "host"
        out.append([name, e - s])
    return out
