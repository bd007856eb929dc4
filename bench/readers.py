"""Reductions that per-layer metric files share. A metric file
(``bench/metrics/<name>.py``) defines ``read(ctx)``, returning the value or
None where the run has nothing to read; ``ctx`` carries the cell, the
reference's sizes (``net``), the mix, the window's records, the trace
reading (`bench.trace.Reading`), the peaks and the engine's counters
before and after the window."""
from __future__ import annotations

from typing import Optional

from bench import counts


def idle_share(ctx) -> Optional[float]:
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 1.0 - t.busy_s / t.window_s


def host_ms_per_step(ctx) -> Optional[float]:
    t = ctx.trace
    if t is None or not t.steps:
        return None
    host = [(end - start) - busy
            for (start, end), busy in zip(t.steps, t.step_busy_s)]
    return 1000.0 * sum(host) / len(host)


def device_ms_per_step(ctx) -> Optional[float]:
    t = ctx.trace
    if t is None or not t.steps:
        return None
    return 1000.0 * sum(t.step_busy_s) / len(t.step_busy_s)


def kernel_roofline(ctx, kernel: str, work) -> Optional[float]:
    """Share of the roofline of ``kernel`` over the window: the operations
    and bytes ``work(step)`` counts for each step against the kernel's
    device time, all chips together."""
    t = ctx.trace
    seconds = t.kernel_s.get(kernel, 0.0) if t is not None else 0.0
    if seconds <= 0 or not ctx.window.steps:
        return None
    ops = nbytes = 0.0
    for step in ctx.window.steps:
        o, b = work(step)
        ops, nbytes = ops + o, nbytes + b
    return counts.roofline_share(ops, nbytes, seconds, ctx.peak)


def step_mfu(ctx) -> Optional[float]:
    """The published network's dense work for every image answered in the
    window, over the device-busy time of the traced window times the
    chips' bf16 peak, in percent: the whole graph's share of the peak
    while the device works, read from the trace and not from the host
    clock."""
    t = ctx.trace
    if t is None or t.busy_s <= 0:
        return None
    images = len(ctx.window.completed())
    if not images:
        return None
    work = counts.dense_flops_per_image(ctx.net) * images
    return 100.0 * work / (ctx.chips * t.busy_s * ctx.peak["flops_per_s"])
