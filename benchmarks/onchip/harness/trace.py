"""The profiler's trace of a traced run, read into a compact summary and
reduced to device busy time, kernel time and idle gaps.

A summary is plain data, so the reduction can be tested on a trimmed
trace recorded on the chip:

    {"window": [start_ns, end_ns],
     "devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
     "host": [[name, start_ns, dur_ns], ...]}

Device events are the operations of each device plane's "XLA Ops" line;
host events are every span of the host's threads (the benchmark's own
`TraceAnnotation`s among them).  The window is the benchmark's
`onchip.traced` annotation.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile

WINDOW = "onchip.traced"
OPS_LINE = "XLA Ops"


class Capture:
    """A profiler trace in a temporary directory.  `start()` and
    `stop()` run the profiler, which blocks the calling thread for a
    while, so both sit outside the measured window; `begin()` and
    `end()` mark the traced sub-window inside it.  `summary()` reads
    the trace, once the window is over, and removes it."""

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="onchip_trace_")
        # no Python function tracing, and only the host's critical
        # spans (the benchmark's own among them): the runtime's finer
        # spans, a few per host-to-device copy, multiply the host's work
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def begin(self) -> None:
        import jax

        self.window = jax.profiler.TraceAnnotation(WINDOW)
        self.window.__enter__()

    def end(self) -> None:
        self.window.__exit__(None, None, None)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def summary(self) -> dict:
        try:
            return read_xplane(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def read_xplane(directory: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    data = ProfileData.from_file(paths[-1])
    devices, host, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    if e.name == WINDOW:
                        window = [e.start_ns, e.start_ns + e.duration_ns]
                    else:
                        host.append([e.name, e.start_ns, e.duration_ns])
    if window is None:
        raise RuntimeError(f"no {WINDOW} span in the trace")
    return {"window": window, "devices": devices, "host": host}


def _clip(events, window):
    lo, hi = window
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def _union(intervals) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window_s(summary: dict) -> float:
    lo, hi = summary["window"]
    return (hi - lo) / 1e9


def busy_s(summary: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    devs = summary["devices"]
    if not devs:
        return 0.0
    total = 0.0
    for events in devs.values():
        spans = _union((s, e) for _, s, e in _clip(events, summary["window"]))
        total += sum(e - s for s, e in spans)
    return total / len(devs) / 1e9


def op_seconds(summary: dict, patterns) -> float:
    """Device seconds of the operations whose name matches any of the
    regular expressions, summed over devices."""
    rx = re.compile("|".join(patterns))
    return sum(e - s for events in summary["devices"].values()
               for name, s, e in _clip(events, summary["window"])
               if rx.search(name)) / 1e9


def label(name: str) -> str:
    """An HLO instruction's name and output type, from the text the
    trace names a device operation by."""
    m = re.match(r"(%\S+) = (\S+?)\{", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def top_ops(summary: dict, n: int = 10) -> list[list]:
    """The `n` device operations that took most time, by name."""
    tally: dict[str, float] = {}
    for events in summary["devices"].values():
        for name, s, e in _clip(events, summary["window"]):
            key = label(name)
            tally[key] = tally.get(key, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(tally.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(summary: dict, n: int = 10) -> list[list]:
    """The `n` longest gaps between device operations on the first
    device, each named by the shortest host span that covers its
    middle (what the host was doing), as [label, seconds]."""
    if not summary["devices"]:
        return []
    events = summary["devices"][sorted(summary["devices"])[0]]
    spans = _union((s, e) for _, s, e in _clip(events, summary["window"]))
    lo, hi = summary["window"]
    edges = [lo] + [x for s, e in spans for x in (s, e)] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:n]
    out = []
    for length, start in gaps:
        mid = start + length / 2
        covering = [(dur, name) for name, s, dur in summary["host"]
                    if s <= mid <= s + dur]
        label = min(covering)[1] if covering else "no host span"
        out.append([label, length / 1e9])
    return out


def trimmed(summary: dict, seconds: float, min_host_ns: float = 0) -> dict:
    """The first `seconds` of the window, and the host spans of at least
    `min_host_ns` in it: a trace small enough to commit for a test."""
    lo = summary["window"][0]
    hi = lo + seconds * 1e9

    def keep(events, least=0):
        return [x for x in events
                if x[1] < hi and x[1] + x[2] > lo and x[2] >= least]
    return {"window": [lo, hi],
            "devices": {k: keep(v) for k, v in summary["devices"].items()},
            "host": keep(summary["host"], min_host_ns)}
