"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The device planes (``/device:TPU:<n>``) hold one event per operation run on
the chip, on their ``XLA Ops`` line.  The benchmark's own host spans are
``jax.profiler.TraceAnnotation`` events whose names start with ``bench.``;
the ``bench.window`` span marks the measured window.  On that window:

* busy time is the union of the operation intervals of each chip, averaged
  over the chips that ran an operation; idle share is one less busy over
  the window;
* the top device operations are those with the most self time (an
  operation's time less that of the operations nested in it, as a loop's
  body ops are in the loop), summed by name over chips and divided by the
  chips used; a name is the HLO instruction's name, result type and opcode;
* the idle gaps are the stretches between busy intervals of any chip, each
  labelled with the innermost benchmark span that holds its midpoint.

The device and host clocks of a trace differ by about a millisecond (in the
recorded v5e trace under ``bench/tests/data`` the device's operations start
about 1.1 ms before the host span that launched them).  The window is the
host's span, so about that much is misattributed at each of its edges:
nothing against a window of tens of seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    chips: int
    top_ops: list        # [[name, seconds], ...]
    idle_gaps: list      # [[label, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def merge(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """Stretches of ``[lo, hi]`` that no interval of ``merged`` covers."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(spans, t: float) -> str:
    """Name of the innermost ``(name, start, end)`` span holding ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "outside_spans"


def short_name(hlo: str) -> str:
    """``%fusion.39 = f32[31401674] fusion`` from a trace's HLO text."""
    text = re.sub(r"\{[^{}]*\}", "", hlo)
    m = re.match(r"(%[\w.\-]+) = (.*?) ([\w\-]+)\(", text)
    if not m:
        return hlo[:80]
    kind = m.group(2)
    kind = kind if len(kind) <= 60 else kind[:57] + "..."
    return f"{m.group(1)} = {kind} {m.group(3)}"


def self_times(events) -> dict[str, float]:
    """Self time of each name, for properly nested ``(name, start, end)``."""
    out: dict[str, float] = {}
    stack: list[tuple[str, float]] = []
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        out[name] = out.get(name, 0.0) + (b - a)
        if stack:
            parent = stack[-1][0]
            out[parent] -= b - a
        stack.append((name, b))
    return out


def read_events(path: str):
    """``({chip: [(op, start_ns, end_ns)]}, [(span, start_ns, end_ns)])``."""
    from jax.profiler import ProfileData

    ops: dict[str, list] = {}
    spans = []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                end = start + float(ev.duration_ns)
                if on_device:
                    ops.setdefault(plane.name, []).append(
                        (ev.name, start, end))
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, start, end))
    return ops, spans


def reduce_events(ops: dict, spans: list, *, top: int = 10) -> Reduced:
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    _, lo, hi = windows[0]
    busy, busy_ns, total, chips = [], 0.0, {}, 0
    for chip_ops in ops.values():
        inside = [(name, max(a, lo), min(b, hi)) for name, a, b in chip_ops
                  if min(b, hi) > max(a, lo)]
        if not inside:
            continue
        chips += 1
        chip_busy = merge((a, b) for _, a, b in inside)
        busy_ns += sum(b - a for a, b in chip_busy)
        busy.extend(chip_busy)
        for name, t in self_times(inside).items():
            key = short_name(name)
            total[key] = total.get(key, 0.0) + t
    if not chips:
        raise ValueError("no device operation ran in the window")
    idle = gaps(merge(busy), lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    top_ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns * 1e-9 / chips,
        chips=chips,
        top_ops=[[name, ns * 1e-9 / chips] for name, ns in top_ops],
        idle_gaps=[[label_at(spans, (a + b) / 2), (b - a) * 1e-9]
                   for a, b in idle[:top]])


def find_xplane(logdir: str) -> str:
    """The newest ``.xplane.pb`` that ``jax.profiler.trace`` wrote."""
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def reduce_trace(path: str, *, top: int = 10) -> Reduced:
    return reduce_events(*read_events(path), top=top)
