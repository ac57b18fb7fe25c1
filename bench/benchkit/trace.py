"""Reduce a profiler trace to device busy time, op times and idle gaps.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. The device planes are named ``/device:TPU:<n>``, and on each the
line ``XLA Ops`` holds one event per operation that ran on that chip. The
benchmark's own host spans (``bench.window``, ``bench.dispatch``,
``bench.wait``, ``bench.fetch``, ``bench.next``) sit on the host plane
``/host:CPU``, on the same clock. Everything below works on plain
``Ev`` records so that it can be checked on a synthetic trace.
"""
from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
DROPPED = "Trace Buffers Dropped"  # the profiler's mark where it lost events
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass(frozen=True)
class Ev:
    name: str
    start: int      # ns
    dur: int        # ns
    meta: str = ""  # the op's string stats (HLO text, op path), one line

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass(frozen=True)
class Trace:
    devices: tuple   # per device: tuple of op ``Ev``, sorted by start
    spans: tuple     # the benchmark's host spans, sorted by start
    window: tuple    # (start, end) of ``bench.window``, ns
    dropped_ns: int = 0  # time in the window for which events were lost

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def _is_device_plane(name: str) -> bool:
    return (name.startswith(DEVICE_PLANE_PREFIX)
            and name[len(DEVICE_PLANE_PREFIX):].isdigit())


def load(trace_dir: str, used) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``; keep the device
    planes of the chips ``used`` (device ids)."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, spans, dropped = {}, [], {}
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            meta: dict[str, str] = {}  # stats read once per op name
            ops = []
            dev = int(plane.name[len(DEVICE_PLANE_PREFIX):])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    dropped[dev] = dropped.get(dev, []) + [
                        Ev(e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events if e.name == DROPPED]
                    continue
                for e in line.events:
                    if e.name not in meta:
                        meta[e.name] = " ".join(
                            str(v) for _, v in e.stats
                            if isinstance(v, str)).replace("\n", " ")
                    ops.append(Ev(e.name, int(e.start_ns), int(e.duration_ns),
                                  meta[e.name]))
            devices[dev] = ops
        elif plane.name == HOST_PLANE:
            spans += [Ev(e.name, int(e.start_ns), int(e.duration_ns))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return build(devices=devices, spans=spans, used=used, dropped=dropped)


def build(devices: dict, spans, used, dropped: dict | None = None) -> Trace:
    """A ``Trace`` from op events by device id and host spans.

    Only the devices in ``used`` that have a plane count: a chip the cell
    does not use, idle on the same host, is no part of its busy time.
    ``dropped`` holds, by device id, the profiler's marks of lost events.
    """
    spans = sorted(spans, key=lambda e: e.start)
    wins = [s for s in spans if s.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    window = (wins[0].start, wins[0].end)
    lost = [covered_ns(clip((dropped or {}).get(i, ()), *window))
            for i in used]
    return Trace(
        devices=tuple(tuple(sorted(devices[i], key=lambda e: e.start))
                      for i in sorted(used) if i in devices),
        spans=tuple(s for s in spans if s.name != WINDOW_SPAN),
        window=window,
        dropped_ns=max(lost, default=0),
    )


def clip(events, lo: int, hi: int) -> list[Ev]:
    """The parts of ``events`` that lie inside ``[lo, hi]``."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Ev(e.name, s, t - s, e.meta))
    return out


def union(events) -> list[tuple[int, int]]:
    """Merged ``(start, end)`` intervals covered by ``events``."""
    merged: list[list[int]] = []
    for e in sorted(events, key=lambda e: e.start):
        if merged and e.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end)
        else:
            merged.append([e.start, e.end])
    return [(s, t) for s, t in merged]


def covered_ns(events) -> int:
    return sum(t - s for s, t in union(events))


def gaps(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals of ``[lo, hi]``: no event of ``events`` runs."""
    out, cur = [], lo
    for s, t in union(clip(events, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def label(gap: tuple[int, int], spans, starts=None) -> str:
    """What the host was doing at the middle of ``gap``: the latest-starting
    benchmark span that covers it, without its ``bench.`` prefix.

    ``spans`` are sorted by start; ``starts`` is their start list.
    """
    mid = (gap[0] + gap[1]) / 2
    if starts is None:
        starts = [s.start for s in spans]
    i = bisect_right(starts, mid) - 1
    while i >= 0:
        if spans[i].end >= mid:
            return spans[i].name[len(SPAN_PREFIX):]
        i -= 1
    return "outside_spans"


def window_ops(trace: Trace) -> list[list[Ev]]:
    """Each device's op events clipped to the measured window."""
    lo, hi = trace.window
    return [clip(ops, lo, hi) for ops in trace.devices]


def per_device_mean(trace: Trace, fn) -> float:
    """Mean over the used devices of ``fn(ops)`` on each one's window ops
    (0 where the trace holds none)."""
    per = [fn(ops) for ops in window_ops(trace)]
    return sum(per) / len(per) if per else 0.0


def busy_ns(trace: Trace) -> float:
    return per_device_mean(trace, covered_ns)


def matching_ns(trace: Trace, pred) -> float:
    """Mean over devices of the summed duration of ops ``e`` where
    ``pred(e)``."""
    return per_device_mean(
        trace, lambda ops: sum(e.dur for e in ops if pred(e)))


_HLO = re.compile(r"%?(\S+) = (.*)")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def short_name(name: str) -> str:
    """An op's HLO text cut to its name, result shape and opcode:
    ``dynamic-update-slice.8 = f32[4096,128] dynamic-update-slice``; a
    tuple result reads ``(...)``. Other names stay as they are."""
    m = _HLO.match(name)
    if not m:
        return name
    rest = m.group(2)
    op = _OPCODE.search(" " + rest)
    shape = "(...)" if rest.startswith("(") else rest.split("{")[0].split()[0]
    return f"{m.group(1)} = {shape} {op.group(1) if op else '?'}"


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` ops that took most device time, mean over devices, each
    under its ``short_name``."""
    tot: dict[str, float] = defaultdict(float)
    ops_per = window_ops(trace)
    for ops in ops_per:
        for e in ops:
            tot[short_name(e.name)] += e.dur / len(ops_per)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_by_host_span(trace: Trace, n: int = 10) -> list[list]:
    """Idle device time in the window, summed by what the host was doing."""
    tot: dict[str, float] = defaultdict(float)
    lo, hi = trace.window
    starts = [s.start for s in trace.spans]
    ops_per = window_ops(trace)
    for ops in ops_per:
        for g in gaps(ops, lo, hi):
            tot[label(g, trace.spans, starts)] += (g[1] - g[0]) / len(ops_per)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]
