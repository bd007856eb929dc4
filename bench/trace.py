"""From a `jax.profiler` trace to device busy time, kernel time and idle
gaps, attributed to the harness's host spans.

Two steps, so that the second can be checked on a small recorded trace
(``bench/tests/data/``):

1. `extract` reads the ``.xplane.pb`` with `jax.profiler.ProfileData`:
   the harness's ``bench.*`` spans from the host planes, and the ops of
   each used chip's ``XLA Ops`` line. A TPU trace names each op by its HLO
   instruction (``%spike_matmul_mapped.11 = f32[...] custom-call(...)``);
   a Pallas kernel is a ``tpu_custom_call`` whose instruction is named
   after the kernel's ``name=``, the name `chip_smoke.kernel_launches`
   reads from the compiled HLO's ``op_name`` (``.../<kernel>/pallas_call``).
2. `reduce` turns that into a `Reading`. Busy time is the union of the op
   intervals on a chip, inside the ``bench.window`` span, averaged over the
   chips; each idle gap is named by the host span around its middle, and a
   gap inside a ``bench.step`` span by where it lies in the step: ``head``
   (before the step's first device op: admission, input upload, dispatch),
   ``mid`` (between ops) or ``tail`` (after its last op: fetching and
   splitting the stats, routing results).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

INSTR_RE = re.compile(r"^%?([\w.\-]+) = ")
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def op_of(event_name: str) -> Tuple[str, str]:
    """(instruction, kernel or "") of a device op event."""
    m = INSTR_RE.match(event_name)
    instr = m.group(1) if m else event_name
    kernel = re.sub(r"\.\d+$", "", instr) if CUSTOM_CALL in event_name else ""
    return instr, kernel


def load(trace_dir: Path):
    """The newest trace under ``trace_dir``, as `jax.profiler.ProfileData`."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def extract(data, device_ids: Sequence[int]) -> Dict:
    """{"spans": [[name, start_s, end_s]], "devices": {id: [[op, start_s,
    end_s, kernel or ""]]}} from a `ProfileData`; times in seconds on the
    trace's clock."""
    spans: List = []
    devices: Dict[int, List] = {}
    for plane in data.planes:
        m = DEVICE_RE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    instr, kernel = op_of(ev.name)
                    start = ev.start_ns * 1e-9
                    ops.append([instr, start, start + ev.duration_ns * 1e-9, kernel])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        start = ev.start_ns * 1e-9
                        spans.append([ev.name, start,
                                      start + ev.duration_ns * 1e-9])
    missing = [d for d in device_ids if d not in devices]
    if missing:
        raise ValueError(f"trace has no op line for devices {missing}")
    spans.sort(key=lambda s: s[1])
    for ops in devices.values():
        ops.sort(key=lambda o: o[1])
    return {"spans": spans, "devices": {str(k): v for k, v in devices.items()}}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Busy:
    """A chip's busy intervals (merged), with the time they cover inside
    any interval in O(log n)."""

    def __init__(self, intervals: Sequence[Interval]):
        self.merged = union(intervals)
        self.starts = [a for a, _ in self.merged]
        self.cum = [0.0]
        for a, b in self.merged:
            self.cum.append(self.cum[-1] + b - a)

    def _upto(self, x: float) -> float:
        """Busy time before ``x``."""
        i = bisect.bisect_right(self.starts, x)
        if i == 0:
            return 0.0
        a, b = self.merged[i - 1]
        return self.cum[i - 1] + min(b, x) - a

    def covered(self, lo: float, hi: float) -> float:
        return self._upto(hi) - self._upto(lo) if hi > lo else 0.0

    def gaps(self, lo: float, hi: float) -> List[Interval]:
        edges = [lo] + [min(max(x, lo), hi) for ab in self.merged for x in ab] + [hi]
        return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


@dataclasses.dataclass
class Reading:
    window_s: float                   # the bench.window span
    busy_s: float                     # mean over chips
    steps: List[Interval]             # bench.step spans, in order
    step_busy_s: List[float]          # busy inside each, mean over chips
    kernel_s: Dict[str, float]        # kernel -> seconds, all chips
    kernel_n: Dict[str, int]          # kernel -> launches, all chips
    op_s: Dict[str, float]            # op -> seconds, mean over chips
    gap_s: Dict[str, float]           # host activity -> idle s, mean over chips

    def breakdown(self, top: int = 10) -> Dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _name_gap(gap: Interval, inner: Sequence, starts: Sequence[float],
              busy: Busy, into: Dict[str, float], weight: float) -> None:
    """Split an idle gap by the harness spans it crosses (the spans inside
    ``bench.window`` do not overlap) and add each piece to ``into`` under
    the span's name; a piece inside a step is named by where in the step it
    lies, a piece outside every span is ``bench.window``."""
    a, b = gap
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while a < b:
        if i < len(inner) and inner[i][2] <= a:
            i += 1
            continue
        if i >= len(inner) or inner[i][1] >= b:
            name, end = "bench.window", b
        elif inner[i][1] > a:
            name, end = "bench.window", inner[i][1]
        else:
            name, lo, hi = inner[i]
            end = min(b, hi)
            if name == "bench.step":
                before = busy.covered(lo, a) > 0
                after = busy.covered(end, hi) > 0
                name += ".mid" if before and after else ".tail" if before else ".head"
        into[name] = into.get(name, 0.0) + (end - a) * weight
        a = end


def reduce(extracted: Dict) -> Reading:
    spans = extracted["spans"]
    windows = [s for s in spans if s[0] == "bench.window"]
    if not windows:
        raise ValueError("trace has no bench.window span")
    _, lo, hi = windows[0]
    inner = sorted((s for s in spans if s[0] != "bench.window"
                    and s[1] >= lo and s[2] <= hi), key=lambda s: s[1])
    starts = [s[1] for s in inner]
    steps = [(a, b) for n, a, b in inner if n == "bench.step"]
    devices = extracted["devices"]
    chips = len(devices)
    busy_s = 0.0
    step_busy = [0.0] * len(steps)
    kernel_s: Dict[str, float] = {}
    kernel_n: Dict[str, int] = {}
    op_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    for ops in devices.values():
        inside = [(name, max(a, lo), min(b, hi), kernel)
                  for name, a, b, kernel in ops if b > lo and a < hi]
        busy = Busy([(a, b) for _, a, b, _ in inside])
        busy_s += busy.cum[-1]
        for i, (a, b) in enumerate(steps):
            step_busy[i] += busy.covered(a, b)
        for name, a, b, kernel in inside:
            op_s[name] = op_s.get(name, 0.0) + (b - a) / chips
            if kernel:
                kernel_s[kernel] = kernel_s.get(kernel, 0.0) + (b - a)
                kernel_n[kernel] = kernel_n.get(kernel, 0) + 1
        for gap in busy.gaps(lo, hi):
            _name_gap(gap, inner, starts, busy, gap_s, 1.0 / chips)
    return Reading(window_s=hi - lo, busy_s=busy_s / chips, steps=steps,
                   step_busy_s=[s / chips for s in step_busy],
                   kernel_s=kernel_s, kernel_n=kernel_n, op_s=op_s,
                   gap_s=gap_s)


def read(trace_dir: Path, device_ids: Sequence[int]) -> Reading:
    return reduce(extract(load(trace_dir), device_ids))
