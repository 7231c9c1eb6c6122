"""Reduce a profiler trace to device busy time, top operations and idle
gaps labelled by what the benchmark's thread was doing.

The reduction works on plain lists so that it can be checked on a
synthetic trace: per chip, the device operations as ``(name, start_s,
end_s)``; on the host, the benchmark's spans the same way. ``load``
makes those lists from the ``.xplane.pb`` file ``jax.profiler`` writes.
The window is the host span ``bench.window``, so host and device
times are read on the profiler's one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_LINES = ("XLA Ops", "XLA Modules")
TOP = 10


@dataclasses.dataclass
class Trace:
    chips: dict[int, list[tuple[str, float, float]]]   # device ops
    spans: list[tuple[str, float, float]]              # host spans


def load(log_dir: str) -> Trace:
    """Device operations and ``bench.*`` host spans of the newest trace
    under ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    chips, spans = {}, []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            name = next((n for n in _OP_LINES if n in lines), None)
            if name is not None:
                chips[int(m.group(1))] = [
                    (_op_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in lines[name].events if e.duration_ns > 0]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(chips=chips, spans=spans)


def _op_name(text: str) -> str:
    """An HLO op's name from the text the trace gives it
    (``%fusion.3 = s8[...] fusion(...)`` -> ``fusion.3``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(gap: tuple[float, float], spans) -> str:
    """The host span that covers most of the gap ('none' if none does);
    the window's own span is not a label."""
    best, best_cover = "none", 0.0
    for name, a, b in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(b, gap[1]) - max(a, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce(trace: Trace, chips: list[int]) -> dict | None:
    """Busy seconds averaged over ``chips`` within the window, the window
    length, the device operations that took most time (seconds summed
    over chips, divided by their number) and the longest idle gaps.
    None when the trace has no window or no operation on those chips."""
    windows = [(a, b) for name, a, b in trace.spans if name == WINDOW_SPAN]
    if not windows or not any(trace.chips.get(c) for c in chips):
        return None
    lo, hi = windows[0]
    busy, ops, gaps = [], {}, []
    for c in chips:
        events = trace.chips.get(c, [])
        merged = union(((a, b) for _, a, b in events), lo, hi)
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in events:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d / len(chips)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": sum(busy) / len(chips),
        "busy_s_per_chip": busy,
        "window_s": hi - lo,
        "device_ops": [[n, s] for n, s in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label(g, trace.spans), g[1] - g[0]] for g in gaps],
    }


def idle_percent(reduced: dict | None) -> float | None:
    """Idle share of the window, in percent, from ``reduce``'s result."""
    if reduced is None or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
