"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU each chip is a
plane ``/device:TPU:<i>`` with an ``XLA Ops`` line (every HLO op as it
ran, named by its HLO text, e.g. ``%fused_forward_full.1 = f32[1032,5]
... custom-call(...)``) and an ``XLA Modules`` line (every program run).
The host is ``/host:CPU``; its threads carry the benchmark's
``TraceAnnotation`` spans on the same clock.

The traced stretch is the benchmark's ``chipbench.window`` span.  Every
device interval is clipped to it.  Busy time is the union of a chip's
op intervals; a kernel's time is the sum of its op durations; the step
programs' time is the union of module intervals.
"""

from __future__ import annotations

import bisect
import dataclasses

WINDOW = "chipbench.window"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: Host spans an idle gap can be laid against; time covered by none is
#: the front end and the load generator between calls.
HOST_SPANS = ("chipbench.dispatch", "chipbench.realize")
UNATTRIBUTED = "loop_and_generator"


def op_short_name(name: str) -> str:
    """``%fused_forward_full.1 = f32[8,5]{...} custom-call(...)`` ->
    ``fused_forward_full.1``; names without HLO text pass through."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals of ``intervals`` clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


@dataclasses.dataclass
class Device:
    name: str
    ops: list          # (start_ns, end_ns, name)
    modules: list      # (start_ns, end_ns, name)


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]          # ns
    devices: list[Device]
    host_spans: list                     # (start_ns, end_ns, name)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, dev: Device) -> list[tuple[float, float]]:
        return union(dev.ops, *self.window)

    def busy_s(self) -> float:
        """Seconds some op ran, mean over the chips."""
        return (sum(total(self.busy(d)) for d in self.devices)
                / len(self.devices) * 1e-9)

    def op_seconds(self, match) -> float:
        """Summed device seconds, over all chips, of ops whose HLO text
        satisfies ``match``."""
        lo, hi = self.window
        return sum(max(0.0, min(e, hi) - max(s, lo))
                   for d in self.devices for s, e, n in d.ops
                   if match(n)) * 1e-9

    def module_seconds(self) -> float:
        """Summed over chips: seconds some program ran on the chip."""
        return sum(total(union(d.modules, *self.window))
                   for d in self.devices) * 1e-9

    def device_ops(self, k: int = 10) -> list:
        """The ``k`` ops that took most device time, by HLO instruction
        name, in seconds per chip."""
        lo, hi = self.window
        agg: dict[str, float] = {}
        for d in self.devices:
            for s, e, n in d.ops:
                t = min(e, hi) - max(s, lo)
                if t > 0:
                    key = op_short_name(n)
                    agg[key] = agg.get(key, 0.0) + t
        n_dev = len(self.devices)
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[name, t * 1e-9 / n_dev] for name, t in top]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle time of the first chip, by what the host was doing: each
        gap between its ops goes to the host span that overlaps it most
        (or to ``loop_and_generator`` when none does).  Seconds per
        activity, largest first, plus the longest single gap, named with
        its activity and its start in seconds after the window opened."""
        busy = self.busy(self.devices[0])
        lo, hi = self.window
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = sorted(self.host_spans)
        starts = [s for s, _, _ in spans]
        agg: dict[str, float] = {}
        longest = (0.0, UNATTRIBUTED, lo)
        for gs, ge in gaps:
            cover: dict[str, float] = {}
            j = max(0, bisect.bisect_right(starts, gs) - 1)
            # spans are short and ordered: walk back over any that began
            # earlier and may still overlap, then forward to the gap's end
            while j > 0 and spans[j - 1][1] > gs:
                j -= 1
            while j < len(spans) and spans[j][0] < ge:
                s, e, name = spans[j]
                o = min(e, ge) - max(s, gs)
                if o > 0:
                    cover[name] = cover.get(name, 0.0) + o
                j += 1
            label = max(cover, key=cover.get) if cover else UNATTRIBUTED
            agg[label] = agg.get(label, 0.0) + (ge - gs)
            if ge - gs > longest[0]:
                longest = (ge - gs, label, gs)
        out = [[name, t * 1e-9] for name, t in
               sorted(agg.items(), key=lambda kv: -kv[1])][:k - 1]
        if longest[0] > 0:
            at = (longest[2] - lo) * 1e-9
            out.append([f"longest_gap:{longest[1]}@{at:.3f}s",
                        longest[0] * 1e-9])
        return out


def device_planes(pd, chips: int) -> list:
    planes = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    planes.sort(key=lambda p: int(p.name[len(DEVICE_PREFIX):]))
    return planes[:chips]


def read(path: str, chips: int) -> Trace | None:
    """The trace at ``path`` reduced to what the metrics read; ``None``
    when it has no window span or no device plane."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    window, spans = None, []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in HOST_SPANS:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    devices = []
    for plane in device_planes(pd, chips):
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            elif line.name == MODULES_LINE:
                modules = [(e.start_ns, e.end_ns, e.name)
                           for e in line.events]
        devices.append(Device(plane.name, ops, modules))
    if window is None or not devices:
        return None
    return Trace(window=window, devices=devices, host_spans=spans)
