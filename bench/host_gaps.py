#!/usr/bin/env python3
"""Idle time of the chip credited to the program's own host spans.

`bench.trace` names each idle gap inside a ``bench.step`` only by where it
lies in the step (``head``, ``mid``, ``tail``). The program marks its host
stages with spans on the profiler's clock (``engine.*`` and ``snn.*``, from
`repro.obs.stages.Stages`). Here each idle piece inside a ``bench.step`` is
credited to the innermost program span around it, and keeps its
``bench.step.head|mid|tail`` name where no program span is; idle time
outside the steps keeps the harness span's name, as in `bench.trace`. The
credited seconds add up to the same idle total as `Reading.gap_s`.

    python3 bench/host_gaps.py --workload <cell> --seed <n> --seconds <s>

runs one cell as `bench/run.py --trace 0` does (its result line goes to
stderr), with the profiler recording the whole run, and prints one JSON
line: the idle gaps both ways, the share of the ``bench.step`` time that
the program's stage spans cover, each stage's milliseconds per step, and,
for every step longer than four times the median, the stage that held it.
A parent program without the spans reads every step gap under its
``bench.step.*`` name, a coverage of 0 and no stages.
"""
from __future__ import annotations

import bisect
import json
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

PROGRAM = ("engine.", "snn.")
STEP = "engine.step"

Span = Tuple[str, float, float]


def program_spans(data) -> List[List]:
    """[[name, start_s, end_s]] of the program's host spans in a
    `ProfileData`, in order of start."""
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM):
                    start = ev.start_ns * 1e-9
                    spans.append([ev.name, start, start + ev.duration_ns * 1e-9])
    spans.sort(key=lambda s: s[1])
    return spans


def innermost(spans: Sequence[Span]) -> List[Span]:
    """Nested spans (one thread's context managers) flattened into
    non-overlapping pieces, each named by the innermost span over it."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []      # (name, end) of open spans
    cursor = 0.0
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= a:
            top, end = stack.pop()
            out.append((top, cursor, end))
            cursor = end
        if stack:
            out.append((stack[-1][0], cursor, a))
        stack.append((name, b))
        cursor = a
    while stack:
        top, end = stack.pop()
        out.append((top, cursor, end))
        cursor = end
    return [s for s in out if s[2] > s[1]]


def _inside(spans: Sequence, steps: Sequence[Tuple[float, float]]) -> List:
    """The spans that lie inside one of ``steps`` (sorted, disjoint)."""
    starts = [a for a, _ in steps]
    out = []
    for s in spans:
        i = bisect.bisect_right(starts, s[1]) - 1
        if i >= 0 and s[2] <= steps[i][1]:
            out.append(s)
    return out


def host_gaps(extracted: Dict, program: Sequence) -> Dict[str, float]:
    """Idle seconds (mean over the chips) by the program span they fall in,
    or by `bench.trace`'s harness name where none is; sums to the idle
    time of the ``bench.window``."""
    spans = extracted["spans"]
    lo, hi = next((a, b) for n, a, b in spans if n == "bench.window")
    inner = sorted((s for s in spans if s[0] != "bench.window"
                    and s[1] >= lo and s[2] <= hi), key=lambda s: s[1])
    starts = [s[1] for s in inner]
    steps = [(a, b) for n, a, b in inner if n == "bench.step"]
    pieces = innermost(_inside(program, steps))
    piece_starts = [p[1] for p in pieces]
    devices = extracted["devices"]
    weight = 1.0 / len(devices)
    out: Dict[str, float] = {}
    for ops in devices.values():
        busy = trace.Busy([(max(a, lo), min(b, hi)) for _, a, b, _ in ops
                           if b > lo and a < hi])
        for a, b in busy.gaps(lo, hi):
            i = max(0, bisect.bisect_right(piece_starts, a) - 1)
            while a < b:
                while i < len(pieces) and pieces[i][2] <= a:
                    i += 1
                if i < len(pieces) and pieces[i][1] <= a:
                    name, _, end = pieces[i]
                    end = min(end, b)
                    out[name] = out.get(name, 0.0) + (end - a) * weight
                else:
                    end = min(b, pieces[i][1]) if i < len(pieces) else b
                    # no program span here: the harness's own name
                    trace._name_gap((a, end), inner, starts, busy, out, weight)
                a = end
    return out


def step_report(extracted: Dict, program: Sequence) -> Dict:
    """Per ``bench.step``: the share of its time the program's stage spans
    (all but ``engine.step``) cover, each stage's ms per step, and the
    stage that held each step longer than four times the median."""
    spans = extracted["spans"]
    lo, hi = next((a, b) for n, a, b in spans if n == "bench.window")
    steps = sorted((a, b) for n, a, b in spans
                   if n == "bench.step" and a >= lo and b <= hi)
    inside = _inside(program, steps)
    starts = [a for a, _ in steps]
    by_step: List[List] = [[] for _ in steps]
    for s in inside:
        by_step[bisect.bisect_right(starts, s[1]) - 1].append(s)
    stage_s: Dict[str, float] = {}
    covered = 0.0
    for own in by_step:
        children = [(a, b) for n, a, b in own if n != STEP]
        covered += sum(b - a for a, b in trace.union(children))
        for n, a, b in own:
            stage_s[n] = stage_s.get(n, 0.0) + (b - a)
    total = sum(b - a for a, b in steps)
    durations = [b - a for a, b in steps]
    median = statistics.median(durations) if durations else 0.0
    long_steps = []
    for (a, b), own in zip(steps, by_step):
        if b - a > 4 * median:
            self_s: Dict[str, float] = {}
            for n, s, e in innermost(own):
                self_s[n] = self_s.get(n, 0.0) + (e - s)
            held = max(self_s.items(), key=lambda kv: kv[1], default=("", 0.0))
            long_steps.append({"ms": 1000.0 * (b - a), "stage": held[0],
                               "stage_ms": 1000.0 * held[1]})
    n = len(steps)
    return {"steps": n,
            "step_ms_mean": 1000.0 * total / n if n else None,
            "step_ms_median": 1000.0 * median,
            "covered_share": covered / total if total else None,
            "stage_ms_per_step": {k: 1000.0 * v / n
                                  for k, v in sorted(stage_s.items())} if n else {},
            "long_steps": long_steps}


def report(data, device_ids: Sequence[int]) -> Dict:
    extracted = trace.extract(data, device_ids)
    program = program_spans(data)
    reading = trace.reduce(extracted)
    gaps = host_gaps(extracted, program)
    top = sorted(gaps.items(), key=lambda kv: -kv[1])
    return dict(step_report(extracted, program),
                window_s=reading.window_s, busy_s=reading.busy_s,
                idle_gaps=reading.breakdown(top=len(reading.gap_s))["idle_gaps"],
                host_gaps=[[k, v] for k, v in top])


def main(argv=None) -> int:
    import argparse
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".jax_cache")
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness, spec
    from repro.launch.compile_cache import enable_compile_cache
    cell = spec.load_cell(args.workload)
    devices = harness.find_devices(cell.chips, require_chip=True)
    enable_compile_cache()
    trace_dir = harness.TRACE_DIR / f"{cell.name}.{args.seed}.gaps"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        harness.run_cell(cell, args.seed, args.seconds, False, T_START,
                         out=sys.stderr)
    finally:
        jax.profiler.stop_trace()
    out = report(trace.load(trace_dir), [d.id for d in devices])
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(dict(out, workload=cell.name, seed=args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
