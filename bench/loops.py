"""The measured window: an offline loop and an open (online) loop around
`EngineCore.step`, with the harness's own spans.

Both loops read the host clock (``time.perf_counter``) and record, per
request, when it was due, when it was submitted and when the harness saw
its result, and per step, when it ran and what its batch carried. The
spans (``bench.window``, ``bench.submit``, ``bench.step``, ``bench.wait``)
land in the profiler's trace when one is recording, and cost a few
microseconds when none is.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

clock = time.perf_counter


def span(name: str, **kw):
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


@dataclasses.dataclass
class Request:
    index: int                  # into the traffic
    due: float                  # host clock
    submitted: float
    done: Optional[float] = None
    step: Optional[int] = None  # engine step that finished it
    status: str = "pending"     # ok | failed | refused | ...
    logits: Optional[np.ndarray] = None
    spikes: Optional[Dict[str, float]] = None


@dataclasses.dataclass
class Step:
    index: int
    start: float
    end: float
    in_spikes: Dict[str, float]  # per layer, summed over the batch
    skip: Dict[str, float]      # batch_skip_rate per mapped layer


@dataclasses.dataclass
class Window:
    start: float
    end: float = 0.0
    requests: List[Request] = dataclasses.field(default_factory=list)
    steps: List[Step] = dataclasses.field(default_factory=list)
    #: engine request id -> request submitted and not yet answered
    outstanding: Dict[int, Request] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def completed(self) -> List[Request]:
        return [r for r in self.requests
                if r.status == "ok" and r.done is not None and r.done <= self.end]


def _run_step(core, outstanding: Dict[int, Request], window: Window) -> None:
    index = core.stats()["steps_run"]
    with span("bench.step", step=index):
        start = clock()
        core.step()
        end = clock()
    in_spikes: Dict[str, float] = {}
    skip: Dict[str, float] = {}
    for rid in list(outstanding):
        res = core.poll(rid)
        if res is None:
            continue
        req = outstanding.pop(rid)
        req.done, req.step, req.status = end, index, res.status
        if res.status != "ok":
            continue
        req.logits = np.asarray(res.outputs)
        req.spikes = dict(res.stats["out_spikes"])
        for k, v in res.stats["in_spikes"].items():
            in_spikes[k] = in_spikes.get(k, 0.0) + v
        skip = res.stats["batch_skip_rate"]
    window.steps.append(Step(index, start, end, in_spikes, skip))


def offline(core, traffic, seconds: float, mix: Dict) -> Window:
    """Keep at least ``slots`` requests queued, never more than
    ``max_queue``, and step until ``seconds`` have passed. The window ends
    with the last step; what is still queued is left to `drain`."""
    slots, cap = int(mix["slots"]), int(mix["max_queue"])
    depth = min(2 * slots, cap)
    i = 0
    window = Window(start=clock())
    outstanding = window.outstanding
    stop = window.start + seconds
    with span("bench.window"):
        while clock() < stop:
            with span("bench.submit"):
                while core.pending() < depth:
                    now = clock()
                    rid = core.submit(traffic.image(i))
                    outstanding[rid] = Request(i, now, now)
                    window.requests.append(outstanding[rid])
                    i += 1
            _run_step(core, outstanding, window)
    window.end = window.steps[-1].end
    return window


def online(core, traffic, seconds: float, mix: Dict) -> Window:
    """Submit each request when it is due, step while any work is queued
    or resident, and wait for the next arrival when none is. A request
    refused at admission (`QueueFull`) is recorded as ``refused``. The
    window ends when the last request has been answered."""
    from repro.serve.api import QueueFull

    window = Window(start=clock())
    outstanding = window.outstanding
    due = window.start + traffic.due_s
    n, nxt = len(due), 0
    with span("bench.window"):
        while nxt < n or outstanding:
            now = clock()
            if nxt < n and due[nxt] <= now:
                with span("bench.submit"):
                    while nxt < n and due[nxt] <= now:
                        req = Request(nxt, float(due[nxt]), clock())
                        window.requests.append(req)
                        try:
                            outstanding[core.submit(traffic.image(nxt))] = req
                        except QueueFull:
                            req.status = "refused"
                        nxt += 1
            if core.pending() or core.in_flight():
                _run_step(core, outstanding, window)
            elif nxt < n:
                with span("bench.wait"):
                    time.sleep(max(0.0, due[nxt] - clock()))
            elif outstanding:
                raise RuntimeError(f"{len(outstanding)} requests neither "
                                   "queued, resident nor answered")
    window.end = clock()
    return window


def drain(core, window: Window) -> None:
    """Answer what the window left queued, for the comparison; these
    steps and answers fall outside the window."""
    steps = len(window.steps)
    while window.outstanding and (core.pending() or core.in_flight()):
        _run_step(core, window.outstanding, window)
    del window.steps[steps:]
