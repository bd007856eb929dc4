"""Multi-process worker fleet tests.

Three layers:

* `serve_connection` driven over in-memory byte streams — the exact
  protocol exchange shape (pushes before the terminal reply, heartbeat
  echoing the step seq) with no subprocess in the loop.
* `SubprocessTransport` against real stub workers — submit/step/poll over
  a pipe, queue-full and option rejection crossing the wire, handshake
  version-mismatch refusal, kill -9 surfacing as `WorkerDied`.
* The supervised router over a worker fleet — a killed worker's in-flight
  requests replay on the survivor; for the LM workload the replayed
  outputs are bit-identical to a fault-free in-process run, the
  acceptance property of the whole process-isolation design.
"""
import dataclasses
import io

import pytest

from repro.configs.base import ArchConfig
from repro.serve.api import EngineConfig, QueueFull, SubmitSpec
from repro.serve.core import EngineCore
from repro.serve.router import make_worker_fleet
from repro.serve.wire import (AckMsg, HeartbeatMsg, HelloMsg, PartialMsg,
                              ProtocolError, ReadyMsg, ResultMsg,
                              ShutdownMsg, StepMsg, SubmitMsg, read_frame,
                              write_frame)
from repro.serve.worker import (RunnerSpec, SubprocessTransport, WorkerDied,
                                build_runner, lm_spec, serve_connection)

STUB = RunnerSpec(kind="stub")
CONFIG = EngineConfig(slots=2, max_queue=4, max_idle_steps=50)


# ---------------------------------------------------------------------------
# serve_connection over in-memory streams: exact protocol shape
# ---------------------------------------------------------------------------

def drive_worker(messages, config=CONFIG):
    inbuf = io.BytesIO()
    write_frame(inbuf, HelloMsg(runner=STUB.to_wire(),
                                config=dataclasses.asdict(config)))
    for msg in messages:
        write_frame(inbuf, msg)
    inbuf.seek(0)
    out = io.BytesIO()
    code = serve_connection(inbuf, out)
    out.seek(0)
    frames = []
    while True:
        frame = read_frame(out)
        if frame is None:
            break
        frames.append(frame)
    return code, frames


def test_protocol_exchange_shape():
    code, frames = drive_worker([SubmitMsg(payload={"steps": 2}),
                                 StepMsg(seq=1), StepMsg(seq=2),
                                 ShutdownMsg()])
    assert code == 0
    ready, ack, *rest = frames
    assert isinstance(ready, ReadyMsg) and ready.workload == "stub"
    assert ack == AckMsg(ok=True, rid=0)
    # step 1: a partial push then the heartbeat echoing seq=1
    assert rest[0] == PartialMsg(rid=0, items=(("tick", 1),))
    assert isinstance(rest[1], HeartbeatMsg) and rest[1].seq == 1
    assert rest[1].in_flight == 1 and rest[1].cost_finite
    # step 2 finishes: partial + result pushes *before* the heartbeat
    assert rest[2] == PartialMsg(rid=0, items=(("tick", 2),))
    assert isinstance(rest[3], ResultMsg)
    assert rest[3].rid == 0 and rest[3].outputs == ("done", 2)
    assert rest[3].status == "ok"
    assert isinstance(rest[4], HeartbeatMsg) and rest[4].seq == 2
    assert rest[4].in_flight == 0
    # shutdown ack is the final frame
    assert rest[5] == AckMsg(ok=True)


def test_worker_eof_is_clean_exit():
    code, frames = drive_worker([SubmitMsg(payload={"steps": 1})])
    assert code == 0                       # parent closing the pipe is fine
    assert isinstance(frames[0], ReadyMsg)


def test_worker_rejects_bad_handshake():
    inbuf = io.BytesIO()
    write_frame(inbuf, StepMsg(seq=1))     # step before hello
    inbuf.seek(0)
    out = io.BytesIO()
    assert serve_connection(inbuf, out) == 2
    out.seek(0)
    reply = read_frame(out)
    assert "expected hello" in reply.error


# ---------------------------------------------------------------------------
# SubprocessTransport against real stub workers
# ---------------------------------------------------------------------------

def test_subprocess_stub_round_trip():
    t = SubprocessTransport(STUB, CONFIG)
    try:
        assert t.stats()["worker_pid"] == t.pid and t.pid > 0
        rid = t.submit_spec(SubmitSpec.make({"steps": 2}))
        assert t.in_flight() == 1          # visible before the first step
        t.step()
        assert t.poll(rid) is None
        t.step()
        res = t.poll(rid)
        assert res.outputs == ("done", 2) and res.status == "ok"
        assert t.poll_partial(rid) == [("tick", 1), ("tick", 2)]
        assert t.in_flight() == 0
        marker = t.progress_marker()
        assert len(marker) == 4 and marker[0] >= 1
        assert t.cost_finite() and t.failed_count() == 0
    finally:
        t.close()
    assert t.proc.returncode == 0          # clean shutdown exchange


def test_queue_full_and_option_rejection_cross_the_wire():
    t = SubprocessTransport(STUB, EngineConfig(slots=1, max_queue=1))
    try:
        t.submit_spec(SubmitSpec.make({"steps": 5}))
        t.step()                           # occupy the slot
        t.submit_spec(SubmitSpec.make({"steps": 5}))
        with pytest.raises(QueueFull):
            t.submit_spec(SubmitSpec.make({"steps": 5}))
        # a raw (client-unvalidated) SubmitSpec still gets rejected by the
        # worker's own submit boundary — validation crosses the wire
        with pytest.raises(ValueError, match="unknown request option"):
            t.submit_spec(SubmitSpec(payload={"steps": 1},
                                     options={"bogus": 1}))
    finally:
        t.close()


def test_handshake_version_mismatch_refused():
    with pytest.raises(ProtocolError, match="rejected handshake.*version"):
        SubprocessTransport(STUB, CONFIG, _hello_version=999)


def test_kill_surfaces_as_workerdied():
    t = SubprocessTransport(STUB, CONFIG, step_timeout_s=10.0)
    rid = t.submit_spec(SubmitSpec.make({"steps": 10}))
    t.step()
    t.kill()
    with pytest.raises(WorkerDied):
        t.step()
    # a dead transport degrades, it does not raise from the read surface
    assert t.cancel(rid) is False
    assert t.poll(rid) is None
    assert t.stats()["worker_dead"] is not None
    with pytest.raises(WorkerDied):
        t.submit_spec(SubmitSpec.make({"steps": 1}))
    t.close()


# ---------------------------------------------------------------------------
# supervised router over worker fleets + chaos
# ---------------------------------------------------------------------------

def test_stub_fleet_reroutes_after_kill():
    router = make_worker_fleet(STUB, 2, CONFIG)
    try:
        rids = [router.submit({"steps": 4}) for _ in range(6)]
        router.step()
        victim = router.replicas[0].transport
        assert victim.in_flight() > 0
        victim.kill()
        results = router.run_until_complete()
        assert [r for r in router.replicas if r.state == "healthy"]
        assert len(router.drain_log) == 1
        for rid in rids:
            assert results[rid].status == "ok"
            assert results[rid].outputs == ("done", 4)
    finally:
        router.close()


LM_CFG = ArchConfig(name="t-fleet", family="dense", n_layers=1, d_model=32,
                    n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab=31,
                    dtype="float32", remat="none", q_chunk=8, kv_chunk=8)
PROMPTS = [[1, 2, 3], [7, 5, 3, 9], [11, 4], [8, 8, 8]]
TOKENS = 4


def test_lm_fleet_kill_replays_bit_identical():
    """The acceptance property: kill -9 a worker mid-stream and every
    request still completes, bit-identical to a fault-free in-process run
    of the same `RunnerSpec`."""
    spec = lm_spec(LM_CFG, seed=0, max_seq=16)
    config = EngineConfig(slots=2, max_queue=8, max_idle_steps=50)

    reference = EngineCore(build_runner(spec), config)
    ref_ids = [reference.submit(p, max_new_tokens=TOKENS) for p in PROMPTS]
    ref_results = reference.run_until_complete()
    expected = [ref_results[rid].outputs for rid in ref_ids]

    router = make_worker_fleet(spec, 2, config, step_timeout_s=300.0)
    try:
        rids = [router.submit(p, max_new_tokens=TOKENS) for p in PROMPTS]
        for _ in range(2):
            router.step()
        victim = router.replicas[0].transport
        assert victim.in_flight() > 0      # killing a worker with work
        victim.kill()
        results = router.run_until_complete()
    finally:
        router.close()
    assert len(router.drain_log) == 1
    assert router.stats()["rerouted"] >= 1
    for rid, want, prompt in zip(rids, expected, PROMPTS):
        assert results[rid].status == "ok"
        assert list(results[rid].outputs) == list(want), prompt


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

def test_ready_reports_the_backend_a_worker_computes_on():
    import jax

    from repro.configs import vgg9_snn
    from repro.serve.worker import snn_spec

    code, frames = drive_worker([ShutdownMsg()])
    assert code == 0 and (frames[0].platform, frames[0].devices) == ("", 0)

    inbuf = io.BytesIO()
    write_frame(inbuf, HelloMsg(runner=snn_spec(vgg9_snn.TINY).to_wire(),
                                config=dataclasses.asdict(CONFIG)))
    write_frame(inbuf, ShutdownMsg())
    inbuf.seek(0)
    out = io.BytesIO()
    assert serve_connection(inbuf, out) == 0
    out.seek(0)
    ready = read_frame(out)
    assert ready.workload == "snn"
    assert ready.platform == jax.default_backend()
    assert ready.devices == jax.device_count()


def test_fleet_larger_than_the_chips_fails_at_launch(monkeypatch):
    """On a TPU the first worker holds every chip; a second one is refused
    before it is spawned, and the first is reaped."""
    spawned = []

    class OnChip:
        platform, devices, pid = "tpu", 4, 4242

        def __init__(self, spec, config, **kwargs):
            spawned.append(self)
            self.closed = False

        def close(self):
            self.closed = True

    monkeypatch.setattr("repro.serve.worker.SubprocessTransport", OnChip)
    with pytest.raises(RuntimeError,
                       match="fleet of 2 workers needs one tpu chip per "
                             "worker.*holds all 4 visible tpu"):
        make_worker_fleet(STUB, 2, CONFIG)
    assert len(spawned) == 1 and spawned[0].closed
