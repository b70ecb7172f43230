"""Reduction of a profiler trace to device busy time, kernel time and the
idle gaps, each gap named by the benchmark's host span it falls in.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes, keeping
only what the reduction needs: the device operations (the "XLA Ops" line of
every ``/device:TPU:<n>`` plane) and the benchmark's own host spans (events
whose name starts with ``bench.``, written by ``jax.profiler.TraceAnnotation``).
:class:`Trace` does the arithmetic on those plain lists, so it can be
checked on a small recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    """Device ops per device as (name, start_s, dur_s), host spans as
    (name, start_s, end_s); all on the trace's clock."""
    ops: dict[str, list[tuple[str, float, float]]]
    spans: list[tuple[str, float, float]]

    def window(self) -> tuple[float, float]:
        """Start and end of the measured window (its host span)."""
        w = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if not w:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        return w[0][1], w[0][2]

    def _clipped(self, device: str):
        lo, hi = self.window()
        for name, start, dur in self.ops[device]:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                yield name, a, b

    def busy_intervals(self, device: str) -> list[tuple[float, float]]:
        """Union of the device's op intervals inside the window."""
        merged: list[list[float]] = []
        for _, a, b in sorted(self._clipped(device), key=lambda e: e[1]):
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return [(a, b) for a, b in merged]

    def window_s(self) -> float:
        lo, hi = self.window()
        return hi - lo

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(d))
                   for d in self.ops) / len(self.ops)

    def op_seconds(self, pattern: str) -> float:
        """Device seconds (summed over devices) of ops whose name matches
        the regular expression ``pattern``."""
        rx = re.compile(pattern)
        return sum(b - a for d in self.ops for name, a, b in self._clipped(d)
                   if rx.search(name))

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` op names that took most device time, with seconds."""
        tot: dict[str, float] = {}
        for d in self.ops:
            for name, a, b in self._clipped(d):
                tot[name] = tot.get(name, 0.0) + (b - a)
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest idle gaps of the first device inside the
        window, each named by the innermost ``bench.*`` span (other than the
        window) that covers its middle."""
        if not self.ops:
            return []
        lo, hi = self.window()
        edges = [lo]
        for a, b in self.busy_intervals(sorted(self.ops)[0]):
            edges += [a, b]
        edges.append(hi)
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        inner = [s for s in self.spans if s[0] != WINDOW_SPAN]
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (a + b) / 2
            cover = [s for s in inner if s[1] <= mid <= s[2]]
            name = (min(cover, key=lambda s: s[2] - s[1])[0] if cover
                    else "no span")
            out.append([name, b - a])
        return out


def op_name(event_name: str) -> str:
    """The op's own name: the trace names a TPU op by its HLO instruction
    (``%copy.212 = u8[...] copy(...)``); keep ``copy.212``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops: dict[str, list] = {}
    spans = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(
                        (op_name(ev.name), ev.start_ns * 1e-9,
                         ev.duration_ns * 1e-9) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                             for ev in line.events
                             if ev.name.startswith("bench."))
    return Trace(ops=ops, spans=spans)
