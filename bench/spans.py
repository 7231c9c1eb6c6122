"""The server's own spans and device programs in a profiler trace.

The program marks its served path with ``serve.*`` spans (the batcher,
quantize, the stage calls, the collector; each ``*.wait`` span marks
blocking) and names each stage's device program
``jit_serve_<model>_<start>_<stop>``. ``load`` reads both from the
``.xplane.pb`` file ``jax.profiler`` writes: the spans with their thread
and arguments, and each chip's "XLA Modules" events. ``reduce`` clips
them to the benchmark's ``bench.window`` span, read by
``bench/trace.py`` on the same clock, and gives:

* ``server_spans``: per span name, the spans that start in the window,
  their seconds, and their work seconds (less the ``*.wait`` spans on
  the same thread inside them);
* ``idle_by_span``: per span name, the device-idle seconds during which
  such a span was open on some thread;
* ``device_by_module``: device seconds and runs per device program.

``bench/trace.py`` keeps its own reduction of the device operations and
the benchmark's spans; this one reads only what that one leaves out.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

from bench.trace import WINDOW_SPAN, _DEVICE_PLANE, Trace, union

SERVER_PREFIX = "serve."
WAIT_SUFFIX = ".wait"
STAGE_MODULE_PREFIX = "jit_serve_"
_MODULE_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class ServerTrace:
    # serve.* spans: (name, start_s, end_s, thread, {argument: value})
    spans: list[tuple[str, float, float, int, dict]]
    # per chip, device programs: (module, start_s, end_s)
    modules: dict[int, list[tuple[str, float, float]]]


def load(log_dir: str) -> ServerTrace:
    """``serve.*`` spans and device programs of the newest trace under
    ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    spans, modules, thread = [], {}, 0
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == _MODULE_LINE:
                    modules[int(m.group(1))] = [
                        (module_name(e.name), e.start_ns * 1e-9,
                         e.end_ns * 1e-9)
                        for e in line.events if e.duration_ns > 0]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                              thread, dict(e.stats))
                             for e in line.events
                             if e.name.startswith(SERVER_PREFIX))
                thread += 1
    return ServerTrace(spans=spans, modules=modules)


def module_name(text: str) -> str:
    """A device program's name without the program id the trace may
    append (``jit_f(123)`` -> ``jit_f``)."""
    return re.sub(r"\(\d+\)$", "", text)


def reduce(trace: Trace, server: ServerTrace, chips: list[int]) -> dict | None:
    """``server_spans``, ``idle_by_span`` and ``device_by_module`` of the
    window (the last two averaged over ``chips``, the ``TOP`` largest).
    None when ``trace`` has no window or the window holds no server span;
    the device keys are empty where the chips ran nothing."""
    windows = [(a, b) for name, a, b in trace.spans if name == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    rows = server_spans(server, lo, hi)
    if not rows:
        return None
    ran = [c for c in chips if trace.chips.get(c)]
    return {"server_spans": rows,
            "idle_by_span": idle_by_span(trace, server, ran, lo, hi),
            "device_by_module": device_by_module(server, ran, lo, hi)}


def server_spans(server: ServerTrace, lo: float, hi: float) -> dict:
    """By span name, and by name and stage as ``name[i]`` where a span
    has a ``stage`` argument: ``n`` spans that start in [lo, hi), ``s``
    their seconds clipped to it, and ``work_s`` those seconds less the
    ``*.wait`` spans on the same thread inside them (0 for a ``*.wait``
    span)."""
    waits: dict[int, list[tuple[float, float]]] = {}
    for name, a, b, thread, _ in server.spans:
        if name.endswith(WAIT_SUFFIX):
            waits.setdefault(thread, []).append((a, b))
    waits = {t: sorted(ws) for t, ws in waits.items()}
    starts = {t: [a for a, _ in ws] for t, ws in waits.items()}
    rows: dict[str, dict] = {}
    for name, a, b, thread, args in server.spans:
        a0, b0 = max(a, lo), min(b, hi)
        if b0 < a0:
            continue
        work = 0.0
        if not name.endswith(WAIT_SUFFIX):
            ws, st = waits.get(thread, []), starts.get(thread, [])
            inside = ws[bisect.bisect_left(st, a):bisect.bisect_left(st, b)]
            work = (b0 - a0) - sum(max(0.0, min(wb, b0) - max(wa, a0))
                                   for wa, wb in inside)
        keys = [name]
        if "stage" in args:
            keys.append(f"{name}[{args['stage']}]")
        for key in keys:
            row = rows.setdefault(key, {"n": 0, "s": 0.0, "work_s": 0.0})
            row["n"] += int(lo <= a < hi)
            row["s"] += b0 - a0
            row["work_s"] += work
    return rows


def _overlap(xs, ys) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_by_span(trace: Trace, server: ServerTrace, chips: list[int],
                 lo: float, hi: float) -> list:
    """``[name, seconds]``: for each span name, the seconds of the window
    in which the chips ran no operation and such a span was open on some
    thread, averaged over ``chips``; the ``TOP`` largest."""
    if not chips:
        return []
    by_name: dict[str, list] = {}
    for name, a, b, _, _ in server.spans:
        by_name.setdefault(name, []).append((a, b))
    busy = [union(((a, b) for _, a, b in trace.chips[c]), lo, hi)
            for c in chips]
    idle = {}
    for name, intervals in by_name.items():
        open_ = union(intervals, lo, hi)
        length = sum(b - a for a, b in open_)
        idle[name] = sum(length - _overlap(open_, m)
                         for m in busy) / len(chips)
    return [[n, s] for n, s in
            sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]]


def device_by_module(server: ServerTrace, chips: list[int], lo: float,
                     hi: float) -> list:
    """``[module, seconds, runs]`` of the device programs that took most
    time in the window: seconds clipped to it and runs that start in it,
    each summed over ``chips`` and divided by their number."""
    seconds: dict[str, float] = {}
    runs: dict[str, float] = {}
    for c in chips:
        for name, a, b in server.modules.get(c, []):
            d = min(b, hi) - max(a, lo)
            if d > 0:
                seconds[name] = seconds.get(name, 0.0) + d / len(chips)
            if lo <= a < hi:
                runs[name] = runs.get(name, 0.0) + 1 / len(chips)
    return [[n, s, runs.get(n, 0.0)] for n, s in
            sorted(seconds.items(), key=lambda kv: -kv[1])[:TOP]]


def per_batch_ms(reduced: dict | None) -> dict:
    """Milliseconds per batch from ``reduce``'s result, each None where
    its spans or programs are absent:

    * ``frontend.dispatch_ms``: the batcher's work, ``serve.assemble``
      and ``serve.dispatch`` less the waits inside, over the batches
      dispatched;
    * ``host.quantize_ms``: the mean ``serve.quantize``;
    * ``pipeline.collect_ms``: the mean ``serve.collect``;
    * ``stage.device_ms``: the slowest stage program's device seconds
      over its runs.
    """
    rows = (reduced or {}).get("server_spans", {})

    def mean(name):
        row = rows.get(name)
        return 1e3 * row["s"] / row["n"] if row and row["n"] else None

    out = {"frontend.dispatch_ms": None,
           "host.quantize_ms": mean("serve.quantize"),
           "pipeline.collect_ms": mean("serve.collect"),
           "stage.device_ms": None}
    n = rows.get("serve.dispatch", {}).get("n")
    if n and "serve.assemble" in rows:
        out["frontend.dispatch_ms"] = 1e3 * (
            rows["serve.assemble"]["work_s"]
            + rows["serve.dispatch"]["work_s"]) / n
    stages = [s / runs for name, s, runs in
              (reduced or {}).get("device_by_module", [])
              if name.startswith(STAGE_MODULE_PREFIX) and runs > 0]
    if stages:
        out["stage.device_ms"] = 1e3 * max(stages)
    return out
