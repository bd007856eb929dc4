"""The readers of the program's host stages: `bench.stages.ms_per_step` on
the engine's counters, and `bench.host_gaps`, which credits the chip's idle
time to the program's spans, on synthetic spans, on the recorded v5e window
(which predates the spans) and on a trace of a TINY engine taken here."""
import gzip
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import host_gaps, stages, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def engine_stats(steps, **seconds):
    return {"steps_run": steps,
            "host_stages": {k.replace("_", ".", 1): {"seconds": v, "calls": steps,
                                                     "max_s": v}
                            for k, v in seconds.items()}}


def test_ms_per_step_reads_the_change_over_the_window():
    ctx = SimpleNamespace(engine=(engine_stats(10, snn_split=1.0),
                                  engine_stats(30, snn_split=2.5, snn_input=0.4)))
    assert stages.ms_per_step(ctx, "snn.split") == pytest.approx(1000 * 1.5 / 20)
    # a stage first entered inside the window counts from zero
    assert stages.ms_per_step(ctx, "snn.input") == pytest.approx(1000 * 0.4 / 20)


@pytest.mark.parametrize("before, after", [
    ({"steps_run": 3}, {"steps_run": 9}),                # a program without spans
    (engine_stats(5, snn_fetch=1.0), engine_stats(5, snn_fetch=1.0)),  # no steps
])
def test_ms_per_step_reads_nothing_where_there_is_nothing(before, after):
    ctx = SimpleNamespace(engine=(before, after))
    assert stages.ms_per_step(ctx, "snn.fetch") is None


def test_innermost_flattens_nested_spans():
    spans = [["engine.step", 0.0, 10.0], ["engine.admit", 0.5, 1.0],
             ["snn.input", 2.0, 3.0], ["snn.dispatch", 3.0, 4.0],
             ["engine.retire", 8.0, 10.0]]
    assert host_gaps.innermost(spans) == [
        ("engine.step", 0.0, 0.5), ("engine.admit", 0.5, 1.0),
        ("engine.step", 1.0, 2.0), ("snn.input", 2.0, 3.0),
        ("snn.dispatch", 3.0, 4.0), ("engine.step", 4.0, 8.0),
        ("engine.retire", 8.0, 10.0)]


def synthetic():
    """A window 0..12 with two steps; the device works 3..5 and 8..9."""
    bench = [["bench.window", 0.0, 12.0], ["bench.submit", 0.0, 1.0],
             ["bench.step", 1.0, 6.0], ["bench.step", 6.5, 11.0]]
    program = [["engine.step", 1.1, 5.9], ["engine.admit", 1.1, 1.5],
               ["snn.input", 1.5, 2.5], ["snn.device_wait", 2.8, 5.0],
               ["snn.split", 5.2, 5.8],
               ["engine.step", 6.6, 10.9], ["snn.fetch", 9.0, 10.0],
               ["engine.step", 11.2, 11.8]]        # outside every bench.step
    ops = [["fusion.1", 3.0, 5.0, ""], ["fusion.2", 8.0, 9.0, ""]]
    return {"spans": bench, "devices": {"0": ops}}, program


def test_idle_time_goes_to_the_innermost_program_span():
    extracted, program = synthetic()
    gaps = host_gaps.host_gaps(extracted, program)
    assert gaps == pytest.approx({
        "bench.submit": 1.0, "bench.window": 0.5 + 1.0,
        "bench.step.head": 0.1 + 0.1, "bench.step.tail": 0.1 + 0.1,
        "engine.admit": 0.4, "snn.input": 1.0, "snn.device_wait": 0.2,
        "engine.step": 0.3 + 0.2 + 0.1 + 1.4 + 0.9, "snn.split": 0.6,
        "snn.fetch": 1.0})
    r = trace.reduce(extracted)
    # every idle second is credited once, and what the steps held stays in them
    assert sum(gaps.values()) == pytest.approx(sum(r.gap_s.values()))
    in_steps = sum(v for k, v in r.gap_s.items() if k.startswith("bench.step."))
    assert sum(v for k, v in gaps.items() if not k.startswith(
        ("bench.submit", "bench.window"))) == pytest.approx(in_steps)


def test_step_report_covers_the_steps_with_the_stage_spans():
    extracted, program = synthetic()
    rep = host_gaps.step_report(extracted, program)
    assert rep["steps"] == 2
    assert rep["step_ms_mean"] == pytest.approx(1000 * (5.0 + 4.5) / 2)
    assert rep["covered_share"] == pytest.approx((0.4 + 1.0 + 2.2 + 0.6 + 1.0) / 9.5)
    assert rep["stage_ms_per_step"]["snn.fetch"] == pytest.approx(500.0)
    assert rep["stage_ms_per_step"]["engine.step"] == pytest.approx(
        1000 * (4.8 + 4.3) / 2)
    assert rep["long_steps"] == []


def recorded():
    from jax.profiler import ProfileData
    path = DATA / "cifar10_fp32.dense.offline.xplane.pb.gz"
    return ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))


def test_a_trace_without_program_spans_keeps_the_harness_names():
    """The recorded v5e window comes from a program without the spans: the
    credited gaps are `bench.trace`'s, to the last bit."""
    data = recorded()
    extracted = trace.extract(data, [0])
    assert host_gaps.program_spans(data) == []
    r = trace.reduce(extracted)
    assert host_gaps.host_gaps(extracted, []) == r.gap_s
    rep = host_gaps.report(data, [0])
    assert rep["covered_share"] == 0 and rep["stage_ms_per_step"] == {}
    assert rep["host_gaps"] == rep["idle_gaps"]
    assert rep["busy_s"] == r.busy_s and rep["window_s"] == r.window_s


def test_program_spans_are_read_from_a_cpu_trace(tmp_path):
    """A TINY engine served under the profiler here: the program's spans
    are in the trace, nested in the harness's, once per step each."""
    import jax
    from repro.configs import vgg9_snn
    from repro.models.vgg9 import init_vgg9
    from repro.serve.api import EngineConfig
    from repro.serve.core import EngineCore
    from repro.serve.runners.snn import SNNRunner
    cfg = vgg9_snn.TINY
    core = EngineCore(SNNRunner(cfg, init_vgg9(jax.random.PRNGKey(0), cfg)),
                      EngineConfig(slots=2))
    img = jax.random.uniform(jax.random.PRNGKey(1),
                             (cfg.img_hw, cfg.img_hw, cfg.in_ch))
    core.submit(img)
    core.run_until_complete()                     # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            core.submit(img)
            with jax.profiler.TraceAnnotation("bench.step"):
                core.step()
    jax.profiler.stop_trace()
    data = trace.load(tmp_path)
    program = host_gaps.program_spans(data)
    names = [s[0] for s in program]
    for name in ("engine.step", "engine.admit", "engine.retire", "snn.input",
                 "snn.dispatch", "snn.device_wait", "snn.fetch", "snn.split",
                 "snn.energy"):
        assert names.count(name) == 2, name
    rep = host_gaps.step_report(trace.extract(data, []), program)
    assert rep["steps"] == 2 and 0 < rep["covered_share"] <= 1
    assert set(rep["stage_ms_per_step"]) == set(names)


STAGES = {"snn_input": "snn.input", "snn_device_wait": "snn.device_wait",
          "snn_fetch": "snn.fetch", "snn_split": "snn.split",
          "snn_energy": "snn.energy", "engine_retire": "engine.retire"}


@pytest.mark.parametrize("cell, mode", [
    ("cifar10_fp32.dense.offline", "offline"),
    ("cifar100_int4.sparse.offline", "offline"),
    ("cifar10_fp32.mixed.poisson", "online")])
def test_each_cell_reads_its_six_stage_metrics(cell, mode):
    """The cell's stage metrics, found by name, read the engine's counters;
    on a program without them (the parent of the spans) they are left out."""
    from bench import harness, spec
    metrics = [m for m in spec.load_cell(cell).per_layer
               if m["source"] == "program_counter" and "_ms_per_step." in m["name"]]
    assert sorted(m["name"] for m in metrics) == sorted(
        f"{k}_ms_per_step.{mode}" for k in STAGES)
    seconds = {k: 0.01 * (i + 1) for i, k in enumerate(STAGES)}
    ctx = SimpleNamespace(engine=(engine_stats(4), engine_stats(14, **seconds)))
    out = harness.per_layer(metrics, ctx)
    for k in STAGES:
        got = out[f"{k}_ms_per_step.{mode}"]
        assert got == {"value": pytest.approx(1000 * seconds[k] / 10), "unit": "ms"}
    parent = SimpleNamespace(engine=({"steps_run": 4}, {"steps_run": 14}))
    assert harness.per_layer(metrics, parent) == {}


def test_idle_time_of_a_recorded_v5e_window_goes_to_the_program_spans():
    """Three 64-slot steps of `cifar10_fp32.dense.offline` traced on one TPU
    v5e with the program's spans: each stage once per step, the stage spans
    cover the steps, and nearly all idle time in the steps has a name."""
    from jax.profiler import ProfileData
    path = DATA / "cifar10_fp32.dense.offline.spans.xplane.pb.gz"
    assert path.stat().st_size < 1 << 20        # the fixture stays small
    data = ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    extracted = trace.extract(data, [0])
    r = trace.reduce(extracted)
    program = host_gaps.program_spans(data)
    names = [s[0] for s in program]
    for name in ("engine.step", "engine.admit", "engine.retire") + tuple(
            STAGES.values()) + ("snn.dispatch",):
        assert names.count(name) == len(r.steps) == 3, name
    gaps = host_gaps.host_gaps(extracted, program)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)
    in_steps = sum(v for k, v in r.gap_s.items() if k.startswith("bench.step."))
    unnamed = sum(v for k, v in gaps.items() if k.startswith("bench.step."))
    assert unnamed < 0.01 * in_steps
    rep = host_gaps.step_report(extracted, program)
    assert rep["covered_share"] >= 0.9
    stages = sum(v for k, v in rep["stage_ms_per_step"].items() if k != "engine.step")
    assert stages == pytest.approx(rep["step_ms_mean"], rel=0.1)
