"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the first timed request): find
the chips, turn on the compile cache, make the weights on the device from
the seed, build `EngineCore` + `SNNRunner` at the mix's slot count, and
warm the one batch shape the window uses (a full batch and a padded one).

``--trace 0`` prints the cell's end-to-end metrics. ``--trace 1`` records
the window with `jax.profiler` and prints the cell's per-layer metrics,
with the device's busy and window seconds and a breakdown.

After the window the device's peak memory is read, the engine is freed and
the plain reference is run over a sample of the answered requests
(`compare`). The compared numbers go to stderr as the last lines and into
the result line under ``checks``, which comes last.
"""
from __future__ import annotations

import gc
import json
import re
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

from bench import compare, counts, loops, spec, stats, traffic as traffic_gen

TRACE_DIR = spec.BENCH / ".traces"


class NoChip(SystemExit):
    """Raised, with exit code 3, when the chips the cell needs are absent."""

    def __init__(self, msg: str):
        print(f"bench: {msg}; no result", file=sys.stderr, flush=True)
        super().__init__(3)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find_devices(chips: int, require_chip: bool) -> List:
    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found platform "
                     f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def weights_key(seed: int):
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def mesh_context(chips: int):
    """A factory of the context the engine steps in: on more than one chip,
    the ``('data',)`` mesh over them, as ``launch/serve.py --data-shard``
    serves."""
    import contextlib
    if chips == 1:
        return contextlib.nullcontext
    from repro.dist.context import compute_mesh
    from repro.launch.mesh import make_data_mesh
    mesh = make_data_mesh(chips)
    return lambda: compute_mesh(mesh)


def build_engine(cell: spec.Cell, params):
    from repro.models.vgg9 import VGG9Config
    from repro.serve.api import EngineConfig
    from repro.serve.core import EngineCore
    from repro.serve.runners.snn import SNNRunner

    model = dict(cell.model, stages=tuple(cell.model["stages"]))
    mix = cell.traffic
    runner = SNNRunner(VGG9Config(**model), params)
    return EngineCore(runner, EngineConfig(
        slots=int(mix["slots"]), max_queue=int(mix["max_queue"]),
        scheduler=mix.get("scheduler", "fifo")))


def warm(core, mix: Dict, hw: int, ch: int) -> None:
    """Compile and run the window's one batch shape: a full batch, then a
    batch with padded slots."""
    slots = int(mix["slots"])
    images = traffic_gen.warmup_images(mix, slots, hw, ch)
    for batch in (images, images[:1]):
        for img in batch:
            core.submit(img)
        core.run_until_complete()


_COMPILES: List[int] = []


def compiles_so_far() -> int:
    """XLA compilations in this process since the first call (there should
    be none in the window)."""
    if not _COMPILES:
        import jax
        _COMPILES.append(0)

        def listener(event: str, *args, **kwargs) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES[0] += 1

        jax.monitoring.register_event_duration_secs_listener(listener)
    return _COMPILES[0]


class HostWatch:
    """What the host did over the window, for finding stalls: the garbage
    collector's pauses by generation and the process's CPU seconds (all
    threads) against the wall clock."""

    def __init__(self):
        self.pauses: List = []          # (generation, seconds)
        self._start = None

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._start = (info["generation"], time.perf_counter())
        elif self._start is not None:
            gen, t0 = self._start
            self.pauses.append((gen, time.perf_counter() - t0))
            self._start = None

    def __enter__(self) -> "HostWatch":
        self.cpu0, self.wall0 = time.process_time(), time.perf_counter()
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)
        self.cpu_s = time.process_time() - self.cpu0
        self.wall_s = time.perf_counter() - self.wall0

    def summary(self, window) -> str:
        by_gen: Dict[int, List[float]] = {}
        for gen, sec in self.pauses:
            by_gen.setdefault(gen, []).append(sec)
        gcs = ", ".join(f"gen{g} {len(v)}x max {1000 * max(v):.1f} ms"
                        for g, v in sorted(by_gen.items())) or "none"
        steps = sorted(s.end - s.start for s in window.steps) or [0.0]
        med = steps[len(steps) // 2]
        slow = [d for d in steps if d > 4 * med]
        return (f"bench: host over the window: cpu {self.cpu_s:.3f} s of wall "
                f"{self.wall_s:.3f} s; gc {gcs}; step ms median "
                f"{1000 * med:.1f} max {1000 * steps[-1]:.1f}; steps over 4x "
                f"the median {len(slow)}, {1000 * sum(slow):.1f} ms together")


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- end-to-end metrics, by name ---------------------------------------------

def end_to_end(name: str, run) -> Optional[float]:
    if name == "setup_s":
        return run.setup_s
    if name == "images_per_s":
        return stats.images_per_s(run.window)
    m = re.fullmatch(r"latency_p(\d+)_ms", name)
    if m:
        lat = stats.latencies_ms(run.window.requests, 1000.0 * run.window.seconds)
        return stats.percentile(lat, float(m.group(1)))
    raise KeyError(f"no end-to-end metric {name!r}")


def per_layer(metrics: List[Dict], ctx,
              bench_dir: Path = spec.BENCH) -> Dict[str, Dict]:
    """Each metric's reader, found by name; one that finds nothing to read
    returns None and is left out."""
    out = {}
    for m in metrics:
        reader = spec.load_module(spec.metric_file(m["name"], bench_dir))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the run -------------------------------------------------------------------

def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             out=sys.stdout) -> Dict:
    import jax

    devices = find_devices(cell.chips, require_chip)
    dev = devices[0]
    peak_table = load_peak(dev.device_kind) if require_chip else None
    log(f"bench: {cell.name} seed {seed} on {dev.platform} {dev.device_kind} "
        f"x{len(devices)}")
    ref = cell.reference
    net = ref.Net.from_model(cell.model)
    mix = cell.traffic
    params = ref.make_weights(weights_key(seed), net)
    jax.block_until_ready(params)
    work = traffic_gen.generate(mix, seed, seconds, net.img_hw, net.in_ch)
    core = build_engine(cell, params)
    mesh = mesh_context(cell.chips)
    with mesh():
        warm(core, mix, net.img_hw, net.in_ch)
    slots = int(mix["slots"])
    b_local = slots // cell.chips
    tiles = counts.mapped_tiles(net, core.runner.plan(b_local), b_local)
    compiles = compiles_so_far()
    before = core.stats()
    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR / f"{cell.name}.{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    loop = loops.online if mix["mode"] == "online" else loops.offline
    with mesh(), HostWatch() as watch:
        window = loop(core, work, seconds, mix)
    if trace:
        jax.profiler.stop_trace()
    after = core.stats()
    in_window = compiles_so_far() - compiles
    with mesh():
        loops.drain(core, window)
    peak = memory_peak(devices)
    del core
    gc.collect()

    log(watch.summary(window))
    limits = compare.limits(cell.config)
    nums = compare.check(ref, params, net, work, window.requests)
    correct = compare.verdict(nums, limits)
    lateness = stats.lateness_ms(window.requests)
    log(f"bench: window {window.seconds:.3f} s, {len(window.steps)} steps, "
        f"{len(window.requests)} requests, {stats.failed(window.requests)} "
        f"failed; compiles in window {in_window}; generator late p50 "
        f"{stats.percentile(lateness, 50):.3f} ms, max {max(lateness):.3f} ms; "
        f"setup {setup_s:.3f} s; compared {nums['compared']} requests, "
        f"largest class-score gap {nums['logit_gap_spikes']} spikes (not compared)")
    log("bench: spike count gap by layer " + " ".join(
        f"{k} {v!r}" for k, v in nums["layer_gaps"].items()))
    run = SimpleNamespace(setup_s=setup_s, window=window)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct,
              "attempted": len(window.requests),
              "failed": stats.failed(window.requests)}
    if trace:
        from bench import trace as trace_red
        reading = trace_red.read(trace_dir, [d.id for d in devices])
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = SimpleNamespace(
            cell=cell, net=net, mix=mix, window=window, trace=reading,
            chips=cell.chips, slots=slots, tiles=tiles,
            peak=peak_table,
            engine=(before, after))
        result["metrics"] = per_layer(cell.per_layer, ctx)
        device["busy_s"] = reading.busy_s
        device["window_s"] = reading.window_s
        result["device"] = device
        result["breakdown"] = reading.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": end_to_end(m["name"], run), "unit": m["unit"]}
            for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = {k: {"value": nums[k], "limit": limits[k]}
                        for k in limits}
    for line in compare.lines(nums, limits):
        log(line)
    print(json.dumps(result), file=out, flush=True)
    return result


def load_peak(kind: str) -> Dict:
    table = json.loads((spec.BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]
