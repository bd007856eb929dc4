"""Host-stage spans (`repro.obs.stages.Stages`): the timer's totals, the
spans `EngineCore` and `SNNRunner` open once per step, their export under
``stats()["host_stages"]`` and as ``stage_<name>_*`` counters."""
import json

import jax
import pytest

from repro.obs import Observability, to_prometheus
from repro.obs import stages as stages_mod
from repro.obs.stages import Stages
from repro.serve.api import EngineConfig
from repro.serve.core import EngineCore

from test_serve_continuous import StubRunner

SNN_STAGES = ("snn.input", "snn.dispatch", "snn.device_wait", "snn.fetch",
              "snn.split", "snn.energy")


def test_stages_accumulate_seconds_calls_and_max_for_nested_spans(monkeypatch):
    ticks = iter([0.0, 1.0, 3.0, 4.0, 10.0, 11.0])
    monkeypatch.setattr(stages_mod, "clock", lambda: next(ticks))
    st = Stages()
    with st.span("outer"):
        with st.span("inner"):
            pass
    with st.span("outer"):
        pass
    snap = st.snapshot()
    assert snap == {"outer": {"seconds": 5.0, "calls": 2, "max_s": 4.0},
                    "inner": {"seconds": 2.0, "calls": 1, "max_s": 2.0}}
    assert json.loads(json.dumps(snap)) == snap


def test_a_span_records_its_call_when_the_body_raises():
    st = Stages()
    with pytest.raises(ValueError):
        with st.span("boom", step_num=3):
            raise ValueError("x")
    assert st.snapshot()["boom"]["calls"] == 1


@pytest.mark.parametrize("admission", ["continuous", "batch"])
def test_engine_spans_once_per_step(admission):
    core = EngineCore(StubRunner(), EngineConfig(slots=2, admission=admission))
    for _ in range(3):
        core.submit({"key": "a", "steps": 1})
    core.run_until_complete()
    steps = core.stats()["steps_run"]
    stages = core.stats()["host_stages"]
    assert steps == 2
    names = ("engine.step", "engine.admit") + (
        ("engine.retire",) if admission == "continuous" else ())
    for name in names:
        assert stages[name]["calls"] == steps
        assert 0 < stages[name]["max_s"] <= stages[name]["seconds"]
    assert stages["engine.step"]["seconds"] >= stages["engine.admit"]["seconds"]


@pytest.fixture(scope="module")
def snn_engine():
    """A TINY `SNNRunner` behind `EngineCore`: 5 images through 2 slots
    (3 steps), with an `Observability` bundle attached."""
    from repro.configs import vgg9_snn
    from repro.models.vgg9 import init_vgg9
    from repro.serve.runners.snn import SNNRunner
    cfg = vgg9_snn.TINY
    runner = SNNRunner(cfg, init_vgg9(jax.random.PRNGKey(0), cfg))
    obs = Observability()
    core = EngineCore(runner, EngineConfig(slots=2), obs=obs)
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    for k in keys:
        core.submit(jax.random.uniform(k, (cfg.img_hw, cfg.img_hw, cfg.in_ch)))
    results = core.run_until_complete()
    assert len(results) == 5 and all(r.status == "ok" for r in results.values())
    return core, obs


def test_snn_stages_run_once_per_engine_step(snn_engine):
    core, _ = snn_engine
    stats = core.stats()
    n = stats["steps_run"]
    assert n == 3
    stages = stats["host_stages"]
    for name in SNN_STAGES + ("engine.step", "engine.admit", "engine.retire"):
        assert stages[name]["calls"] == n, name
        assert stages[name]["seconds"] > 0, name
    # the runner's stages lie inside the engine's step
    inner = sum(stages[name]["seconds"] for name in SNN_STAGES)
    assert inner < stages["engine.step"]["seconds"]
    assert json.loads(json.dumps(stats))["host_stages"] == stages


def test_stage_counters_reach_the_metrics_registry(snn_engine):
    core, obs = snn_engine
    snap = obs.metrics.snapshot()
    stages = core.host_stages()
    for name, totals in stages.items():
        key = name.replace(".", "_")
        assert snap[f"stage_{key}_seconds"]["kind"] == "counter"
        assert snap[f"stage_{key}_seconds"]["value"] == pytest.approx(
            totals["seconds"])
        assert snap[f"stage_{key}_calls"]["value"] == totals["calls"]
    # a second snapshot with no new work leaves the counters where they are
    assert obs.metrics.snapshot()["stage_snn_fetch_calls"]["value"] == 3
    assert "# TYPE stage_snn_split_seconds counter" in to_prometheus(snap)
