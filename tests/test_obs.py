"""Observability plane (repro.obs): units + the no-perturbation contract.

Four layers:

* Unit coverage of the three pillars — typed metrics registry (kind
  clashes, Prometheus rendering, fleet aggregation), tracer (span
  lifecycle, coalescing, drain increments, merge namespacing), flight
  recorder (bounded rings, postmortem dumps).
* The acceptance property of the whole subsystem, asserted bit-identically
  for the real LM and SNN runners across seeds: serving with the
  observability bundle attached produces exactly the same `Result`s and
  the same admission decisions as serving detached.
* The fleet story: an in-process router drain carries marker/cost_finite
  detail (always) and a flight-recorder dump (when observed); a 2-worker
  *subprocess* stub fleet merges every worker's spans and metrics into one
  cross-process trace via heartbeat telemetry.
* The perf-gate + schema satellites: `benchmarks.run.check_gate` lineage
  logic, `benchmarks.common.append_result` duplicate suppression, and the
  schema checker's `serve_engine_obs` validator + duplicate rejection.
"""
import importlib.util
import json
import os

import jax
import pytest

from repro.configs.base import ArchConfig
from repro.models import transformer as tf
from repro.obs import (FlightRecorder, MetricsRegistry, Observability,
                       Tracer, aggregate, merge_traces, to_prometheus)
from repro.serve.api import EngineConfig
from repro.serve.core import EngineCore, StepClock
from repro.serve.faults import parse_fleet_plan
from repro.serve.router import make_router, make_worker_fleet
from repro.serve.worker import RunnerSpec

from test_serve_continuous import StubRunner


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_typed_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("c", "help c").inc(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h", buckets=(0.1, 1.0)).observe(0.5)
    with pytest.raises(TypeError):          # kind clash on a known name
        reg.gauge("c")
    with pytest.raises(ValueError):         # counters are monotonic
        reg.counter("c").inc(-1)
    snap = reg.snapshot()
    assert snap["c"] == {"kind": "counter", "value": 2.0, "help": "help c"}
    text = to_prometheus(snap)
    assert "# TYPE c counter" in text and "\nc 2" in text
    assert 'h_bucket{le="0.1"} 0' in text
    assert 'h_bucket{le="1.0"} 1' in text and "h_count 1" in text
    labelled = to_prometheus(snap, labels={"replica": "3"})
    assert 'c{replica="3"} 2' in labelled


def test_registry_collectors_pull_at_snapshot():
    reg = MetricsRegistry()
    state = {"ewma": 0.25}
    reg.collectors.append(
        lambda r: r.gauge("skip_ewma").set(state["ewma"]))
    assert reg.snapshot()["skip_ewma"]["value"] == 0.25
    state["ewma"] = 0.75                    # observed lazily, not cached
    assert reg.snapshot()["skip_ewma"]["value"] == 0.75


def test_aggregate_sums_and_per_replica_breakdown():
    r0, r1 = MetricsRegistry(), MetricsRegistry()
    r0.counter("steps").inc(3)
    r1.counter("steps").inc(4)
    r0.gauge("depth").set(2)
    r1.gauge("depth").set(5)
    r0.histogram("lat", buckets=(1.0,)).observe(0.5)
    r1.histogram("lat", buckets=(1.0,)).observe(2.0)
    agg = aggregate({0: r0.snapshot(), 1: r1.snapshot()})
    assert agg["steps"]["value"] == 7
    assert agg["depth"]["value"] == 7
    assert agg["depth"]["per_replica"] == {"0": 2.0, "1": 5.0}
    assert agg["lat"]["count"] == 2 and agg["lat"]["sum"] == 2.5
    r2 = MetricsRegistry()
    r2.gauge("steps").set(1)                # counter elsewhere
    with pytest.raises(TypeError):
        aggregate({0: r0.snapshot(), 2: r2.snapshot()})


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_span_lifecycle():
    tr = Tracer()
    tr.begin(0, 0, 0.0, priority=1)
    tr.admit(0, 1, 1.0)
    tr.phase(0, "prefill", 1, 1.0, units=4)
    tr.phase(0, "prefill", 2, 2.0, units=4)
    tr.phase(0, "decode", 3, 3.0, units=1)
    tr.phase(0, "decode", 4, 4.0, units=1)
    tr.end(0, "ok", 5, 5.0)
    by_name = {}
    for s in tr.export():
        by_name.setdefault(s["name"], []).append(s)
    root, = by_name["request"]
    assert root["status"] == "ok" and root["end_step"] == 5
    assert root["attrs"] == {"priority": 1}
    queued, = by_name["queued"]
    assert queued["parent_id"] == root["span_id"]
    assert (queued["start_step"], queued["end_step"]) == (0, 1)
    serve, = by_name["serve"]
    assert serve["parent_id"] == root["span_id"] and serve["end_step"] == 5
    assert len(by_name["prefill-chunk"]) == 2       # one span per chunk step
    assert all(c["end_step"] is not None for c in by_name["prefill-chunk"])
    decode, = by_name["decode"]                     # contiguous run coalesced
    assert (decode["start_step"], decode["end_step"]) == (3, 4)
    assert decode["attrs"]["units"] == 2
    assert all(s["request_id"] == 0 for s in tr.export())


def test_tracer_queue_retirement_and_unknown_rids():
    tr = Tracer()
    tr.begin(7, 0, 0.0)
    tr.end(7, "expired", 3, 3.0)            # retired from the queue
    spans = {s["name"]: s for s in tr.export()}
    assert spans["request"]["status"] == "expired"
    assert spans["queued"]["end_step"] == 3
    tr.phase(99, "decode", 1, 1.0)          # unknown rid: ignored
    tr.end(99, "ok", 1, 1.0)
    assert len(tr.export()) == 2


def test_tracer_drain_ships_increments():
    tr = Tracer()
    tr.begin(0, 0, 0.0)
    tr.admit(0, 1, 1.0)                     # closes 'queued'
    first = tr.drain()
    assert [s["name"] for s in first] == ["queued"]
    assert tr.drain() == []                 # an increment, not a repeat
    tr.end(0, "ok", 2, 2.0)
    names = sorted(s["name"] for s in tr.drain())
    assert names == ["request", "serve"]
    assert tr.drain() == []


def test_merge_traces_namespaces_ids():
    a = Tracer()
    a.begin(0, 0, 0.0)
    a.end(0, "ok", 1, 1.0)
    b = Tracer()
    b.begin(0, 0, 0.0)                      # same local ids as a's
    b.end(0, "failed", 2, 2.0)
    merged = merge_traces([(0, a.export()), (1, b.export())])
    ids = {s["span_id"] for s in merged}
    assert len(ids) == len(merged) == 4     # no collisions after namespacing
    assert all(s["parent_id"] in ids for s in merged
               if s["parent_id"] is not None)
    assert {s["replica"] for s in merged} == {0, 1}


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

class _Report:
    """Minimal StepReport stand-in for ring tests."""

    def __init__(self, units):
        self.cost = {"units": units}
        self.finished = {}
        self.progress = {}


def test_recorder_ring_is_bounded_and_dumps():
    rec = FlightRecorder(capacity=3)
    for step in range(5):
        rec.record(step, _Report(step), seconds=0.1, queue_len=1, occupied=2)
        rec.note(step, "admit", rids=[step])
    assert [f["step"] for f in rec.frames] == [2, 3, 4]
    assert rec.tail(2)[-1]["cost"] == {"units": 4}
    dump = rec.dump("stalled", extra={"resident": [7]})
    assert dump["reason"] == "stalled" and dump["step"] == 4
    assert len(dump["frames"]) == 3 and dump["resident"] == [7]
    assert [n["step"] for n in dump["notes"]] == [2, 3, 4]
    assert rec.dumps == [dump]


# ---------------------------------------------------------------------------
# No-perturbation contract: attached == detached, bit-identically
# ---------------------------------------------------------------------------

LM_CFG = ArchConfig(name="t-obs", family="dense", n_layers=2, d_model=32,
                    n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab=61,
                    dtype="float32", remat="none", q_chunk=8, kv_chunk=8)


@pytest.mark.parametrize("seed", [0, 1])
def test_lm_bit_identical_with_obs_attached(seed):
    from repro.serve.runners.lm import LMRunner
    params = tf.init_params(jax.random.PRNGKey(seed), LM_CFG)
    runner = LMRunner(LM_CFG, params, max_seq=32)
    prompts = [[1 + seed, 2, 3], [7, 5], [4, 4, 4, 4]]

    def serve(obs):
        core = EngineCore(runner, EngineConfig(slots=2, prefill_chunk=2),
                          clock=StepClock(), obs=obs)
        rids = [core.submit(p, max_new_tokens=5) for p in prompts]
        results = core.run_until_complete()
        return [results[r] for r in rids], list(core.admission_log)

    plain, log_plain = serve(None)
    obs = Observability()
    observed, log_obs = serve(obs)
    assert [r.outputs for r in observed] == [r.outputs for r in plain]
    assert [r.status for r in observed] == [r.status for r in plain]
    assert [dict(r.stats) for r in observed] == [dict(r.stats) for r in plain]
    assert log_obs == log_plain             # identical admission decisions
    # ... and the attached bundle really observed the run
    roots = [s for s in obs.tracer.export() if s["name"] == "request"]
    assert len(roots) == len(prompts)
    assert {s["status"] for s in roots} == {"ok"}
    chunks = [s for s in obs.tracer.export() if s["name"] == "prefill-chunk"]
    assert len(chunks) == sum(dict(r.stats)["prefill_chunks"] for r in plain)
    snap = obs.metrics.snapshot()
    assert snap["engine_retired_ok"]["value"] == len(prompts)
    assert snap["engine_decode_tokens"]["value"] == sum(
        dict(r.stats)["new_tokens"] for r in plain)
    assert len(obs.recorder.frames) > 0


@pytest.mark.parametrize("seed", [0, 1])
def test_snn_bit_identical_with_obs_attached(seed):
    from repro.configs import vgg9_snn
    from repro.models.vgg9 import init_vgg9
    from repro.serve.runners.snn import SNNRunner
    cfg = vgg9_snn.TINY
    params = init_vgg9(jax.random.PRNGKey(seed), cfg)
    runner = SNNRunner(cfg, params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 10), 3)
    imgs = [jax.random.uniform(k, (cfg.img_hw, cfg.img_hw, cfg.in_ch))
            for k in keys]
    imgs[0] = imgs[0] * 0.02                # near-silent: sparse class

    def serve(obs):
        core = EngineCore(runner,
                          EngineConfig(slots=2, scheduler="sparsity"),
                          obs=obs)
        rids = [core.submit(img, source="sparse" if i == 0 else "dense")
                for i, img in enumerate(imgs)]
        results = core.run_until_complete()
        return [results[r] for r in rids], list(core.admission_log)

    plain, log_plain = serve(None)
    obs = Observability()
    observed, log_obs = serve(obs)
    for a, b in zip(observed, plain):
        assert a.status == b.status == "ok"
        assert (a.outputs == b.outputs).all()
        assert dict(a.stats) == dict(b.stats)
    # same scheduler (batch-composition) decisions, step by step
    assert log_obs == log_plain
    snap = obs.metrics.snapshot()
    assert "scheduler_skip_ewma_global" in snap      # sparsity EWMAs pulled
    assert snap["engine_retired_ok"]["value"] == len(imgs)
    assert snap["precision_served_energy_eq3_j"]["value"] > 0


# ---------------------------------------------------------------------------
# Fleet: drain detail, recorder dump on wedge, cross-process merge
# ---------------------------------------------------------------------------

def _drive_router(router, rids, max_steps=200):
    for _ in range(max_steps):
        router.step()
        if not router._outstanding:
            break
    return {rid: router.poll(rid) for rid in rids}


def test_wedge_drain_detail_carries_dump_when_observed():
    plans = parse_fleet_plan("0=wedge@2")
    router = make_router(StubRunner(), 2, EngineConfig(slots=2, max_queue=8),
                         plans=plans, wedge_patience=2, obs=True)
    rids = [router.submit({"key": "a", "steps": 6}, affinity="a")
            for _ in range(2)]
    results = _drive_router(router, rids)
    assert all(results[r].status == "ok" for r in rids)
    entry, = router.drain_log
    assert len(entry) == 5
    step, idx, condition, rerouted, detail = entry
    assert idx == 0 and condition == "wedged" and rerouted
    assert isinstance(detail["marker"], tuple)       # heartbeat evidence
    assert detail["cost_finite"] is True
    dump = detail["dump"]                            # recorder postmortem
    assert dump["reason"] == "wedged" and dump["frames"]
    assert dump["frames"][-1]["step"] >= 0
    tel = router.telemetry()
    assert tel["dumps"] and tel["metrics"]["router_drains"]["value"] == 1


def test_wedge_drain_detail_without_obs_has_no_dump():
    plans = parse_fleet_plan("0=wedge@2")
    router = make_router(StubRunner(), 2, EngineConfig(slots=2, max_queue=8),
                         plans=plans, wedge_patience=2)
    rids = [router.submit({"key": "a", "steps": 6}, affinity="a")
            for _ in range(2)]
    results = _drive_router(router, rids)
    assert all(results[r].status == "ok" for r in rids)
    detail = router.drain_log[0][4]
    assert "marker" in detail and "cost_finite" in detail
    assert detail.get("dump") is None


def test_worker_fleet_merges_cross_process_telemetry():
    fleet = make_worker_fleet(RunnerSpec(kind="stub"), 2,
                              EngineConfig(slots=2, max_queue=8,
                                           max_idle_steps=50), obs=True)
    try:
        rids = [fleet.submit({"steps": 2}) for _ in range(4)]
        results = fleet.run_until_complete()
        tel = fleet.telemetry()
    finally:
        fleet.close()
    assert all(results[r].status == "ok" for r in rids)
    spans = tel["trace"]
    replicas = {str(s["replica"]) for s in spans}
    assert "router" in replicas and len(replicas) >= 3   # both workers traced
    ids = {s["span_id"] for s in spans}
    assert all(s["parent_id"] in ids for s in spans
               if s["parent_id"] is not None)            # merge kept lineage
    roots = [s for s in spans
             if s["name"] == "request" and s["replica"] == "router"]
    assert len(roots) == 4 and all(r["status"] == "ok" for r in roots)
    agg = tel["metrics"]
    assert agg["router_submitted"]["value"] == 4
    assert agg["engine_steps"]["kind"] == "counter"
    assert agg["engine_retired_ok"]["value"] == 4


def test_wire_telemetry_is_incremental():
    obs = Observability()
    obs.on_submit(0, 0, 0.0)
    obs.on_admit([0], 0, 0.0)
    t1 = obs.wire_telemetry()
    assert [s["name"] for s in t1["spans"]] == ["queued"]
    assert "engine_admitted" in t1["metrics"]
    t2 = obs.wire_telemetry()
    assert t2["spans"] == []                # only newly closed spans ship
    dump = obs.on_dump("stalled", 3, resident=[0])
    assert dump["reason"] == "stalled"
    t3 = obs.wire_telemetry()
    assert [d["reason"] for d in t3["dumps"]] == ["stalled"]
    assert "dumps" not in obs.wire_telemetry()           # shipped once


# ---------------------------------------------------------------------------
# Satellites: perf gate, duplicate suppression, schema checker
# ---------------------------------------------------------------------------

def _bench_rec(name, us, cfg="x", ts=0):
    return {"name": name, "config": {"derived": cfg},
            "metrics": {"us_per_call": us}, "timestamp": ts}


def test_perf_gate_flags_lineage_regressions():
    from benchmarks.run import check_gate
    data = [_bench_rec("a", 100.0), _bench_rec("a", 90.0),
            _bench_rec("a", 130.0)]
    regs = check_gate(data, threshold=0.2)
    assert regs == [("a", json.dumps({"derived": "x"}, sort_keys=True),
                     90.0, 130.0)]
    # within threshold / single run / different config: never a regression
    assert check_gate([_bench_rec("a", 100.0), _bench_rec("a", 119.0)]) == []
    assert check_gate([_bench_rec("a", 100.0)]) == []
    assert check_gate([_bench_rec("a", 100.0),
                       _bench_rec("a", 500.0, cfg="y")]) == []
    # untimed records (us_per_call=0, e.g. serve_engine) are skipped
    assert check_gate([_bench_rec("s", 0.0), _bench_rec("s", 0.0)]) == []


def test_append_result_drops_exact_duplicates(tmp_path, monkeypatch):
    import benchmarks.common as common
    path = tmp_path / "results.json"
    monkeypatch.setattr(common, "RESULTS_PATH", str(path))
    rec = {"name": "x", "config": {"c": "1"},
           "metrics": {"us_per_call": 1.0}, "timestamp": 5}
    common.append_result(dict(rec))
    common.append_result(dict(rec))                 # double-append: dropped
    common.append_result(dict(rec, timestamp=6))    # new event: kept
    assert len(json.loads(path.read_text())) == 2


def _schema_checker():
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "check_bench_schema.py")
    spec = importlib.util.spec_from_file_location("check_bench_schema", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_schema_checker_obs_record_and_duplicates(tmp_path):
    mod = _schema_checker()
    obs_rec = {"name": "serve_engine_obs", "config": {"derived": "d"},
               "metrics": {"us_per_call": 0.0, "workers": 2,
                           "obs": {"wall_s": 0.1, "step_ms": 1.0,
                                   "overhead_x": 1.1,
                                   "merged_trace_spans": 40,
                                   "engine_steps": 20,
                                   "trace_replicas": ["0", "router"],
                                   "bit_identical": True}},
               "timestamp": 1}
    assert mod.check_record(obs_rec) == []
    broken = json.loads(json.dumps(obs_rec))
    del broken["metrics"]["obs"]["bit_identical"]
    broken["metrics"]["obs"]["trace_replicas"] = "router"
    problems = mod.check_record(broken)
    assert any("bit_identical" in p for p in problems)
    assert any("trace_replicas" in p for p in problems)
    # duplicate (name, config, timestamp) records fail the file check
    dup = tmp_path / "dup.json"
    dup.write_text(json.dumps([obs_rec, obs_rec]))
    assert mod.check_file(str(dup)) == 1
    solo = tmp_path / "solo.json"
    solo.write_text(json.dumps([obs_rec,
                                dict(obs_rec, timestamp=2)]))
    assert mod.check_file(str(solo)) == 0
