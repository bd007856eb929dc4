"""The reduction from a profiler trace to busy time, kernel time and idle
gaps: on synthetic intervals, on a trace taken here on the CPU (host spans
only), and on a small window recorded on a TPU v5e (``data/``)."""
import gzip
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def test_busy_is_the_union_of_op_intervals():
    busy = trace.Busy([(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.7)])
    assert busy.merged == [(0.0, 3.0), (5.0, 6.0)]
    assert busy.cum[-1] == pytest.approx(4.0)
    assert busy.covered(2.5, 5.5) == pytest.approx(1.0)
    assert busy.gaps(-1.0, 7.0) == [(-1.0, 0.0), (3.0, 5.0), (6.0, 7.0)]


def test_kernel_name_from_the_instruction():
    call = ('%lif_epilogue.3 = (f32[8,128]) custom-call(f32[8,128] %a), '
            'custom_call_target="tpu_custom_call"')
    assert trace.op_of(call) == ("lif_epilogue.3", "lif_epilogue")
    assert trace.op_of("%fusion.12 = f32[8] fusion(f32[8] %p)") == ("fusion.12", "")


def synthetic():
    # window 0..10; two steps; ops on one chip: a kernel in each step
    spans = [["bench.window", 0.0, 10.0], ["bench.submit", 0.0, 1.0],
             ["bench.step", 1.0, 5.0], ["bench.wait", 5.0, 6.0],
             ["bench.step", 6.0, 10.0]]
    ops = [["dense_conv_lif.1", 2.0, 3.0, "dense_conv_lif"], ["fusion.1", 2.5, 3.5, ""],
           ["dense_conv_lif.1", 7.0, 8.0, "dense_conv_lif"], ["copy.2", 9.0, 9.5, ""]]
    return {"spans": spans, "devices": {"0": ops}}


def test_reduce_on_synthetic_intervals():
    r = trace.reduce(synthetic())
    assert r.window_s == 10.0 and r.busy_s == pytest.approx(3.0)
    assert r.steps == [(1.0, 5.0), (6.0, 10.0)]
    assert r.step_busy_s == pytest.approx([1.5, 1.5])
    assert r.kernel_s == {"dense_conv_lif": 2.0}
    assert r.kernel_n == {"dense_conv_lif": 2}
    assert r.gap_s == pytest.approx({
        "bench.submit": 1.0, "bench.step.head": 2.0, "bench.step.tail": 2.0,
        "bench.wait": 1.0, "bench.step.mid": 1.0})
    assert sum(r.gap_s.values()) == pytest.approx(r.window_s - r.busy_s)
    b = r.breakdown(top=2)
    assert b["device_ops"][0] == ["dense_conv_lif.1", 2.0]
    assert len(b["idle_gaps"]) == 2 and b["idle_gaps"][0][1] == 2.0


def test_busy_is_averaged_over_chips():
    ex = synthetic()
    ex["devices"]["1"] = [["dense_conv_lif.3", 1.0, 5.0, "dense_conv_lif"]]
    r = trace.reduce(ex)
    assert r.busy_s == pytest.approx((3.0 + 4.0) / 2)
    assert r.kernel_s["dense_conv_lif"] == pytest.approx(6.0)


def test_host_spans_are_read_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    data = trace.load(tmp_path)
    with pytest.raises(ValueError, match="no op line"):
        trace.extract(data, [0])          # a CPU has no TPU plane
    ex = trace.extract(data, [])
    names = [s[0] for s in ex["spans"]]
    assert names.count("bench.step") == 2 and names.count("bench.window") == 1
    window = next(s for s in ex["spans"] if s[0] == "bench.window")
    assert window[2] - window[1] >= 0.004


def test_idle_time_between_harness_spans_is_the_loops_own():
    ex = {"spans": [["bench.window", 0.0, 4.0], ["bench.step", 1.0, 2.0]],
          "devices": {"0": [["lif_epilogue.2", 1.2, 1.8, "lif_epilogue"]]}}
    r = trace.reduce(ex)
    assert r.gap_s == pytest.approx({"bench.window": 3.0, "bench.step.head": 0.2,
                                     "bench.step.tail": 0.2})


def recorded():
    from jax.profiler import ProfileData
    path = DATA / "cifar10_fp32.dense.offline.xplane.pb.gz"
    assert path.stat().st_size < 1 << 20        # the fixture stays small
    return ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))


def test_reduction_on_a_recorded_v5e_window():
    """A few 64-slot steps of `cifar10_fp32.dense.offline` traced on one
    TPU v5e by `bench/run.py --trace 1`."""
    ex = trace.extract(recorded(), [0])
    r = trace.reduce(ex)
    n = len(r.steps)
    assert n >= 2
    # kernels found by name: one dense core, six sparse cores and sixteen
    # LIF epilogues (eight spiking layers x T = 2) per step
    assert r.kernel_n == {"dense_conv_lif": n, "spike_matmul_mapped": 6 * n,
                          "lif_epilogue": 16 * n}
    assert all(r.kernel_s[k] > 0 for k in r.kernel_n)
    # busy is the union of the op intervals, not their sum
    _, lo, hi = next(s for s in ex["spans"] if s[0] == "bench.window")
    ops = [(max(a, lo), min(b, hi)) for _, a, b, _ in ex["devices"]["0"]
           if b > lo and a < hi]
    merged = trace.union(ops)
    assert r.busy_s == pytest.approx(sum(b - a for a, b in merged))
    assert r.busy_s <= sum(b - a for a, b in ops)
    assert 0 < r.busy_s < r.window_s
    assert sum(r.step_busy_s) <= r.busy_s + 1e-9
    # every idle second is attributed to a harness span
    assert sum(r.gap_s.values()) == pytest.approx(r.window_s - r.busy_s)
    assert set(r.gap_s) <= {"bench.step.head", "bench.step.mid",
                            "bench.step.tail", "bench.submit", "bench.window"}
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][0].startswith(
        "spike_matmul_mapped.")


def test_step_mfu_reads_the_device_busy_time_not_the_host_clock():
    """The graph's share of the peak: the dense work of the images answered
    in the window over the trace's busy time, whatever the window's length
    on the host clock."""
    from types import SimpleNamespace
    from bench import counts, loops, readers
    from bench.references.vgg9 import Net
    import json
    model = json.loads((ROOT / "bench/configs/vgg9_cifar10_fp32.json").read_text())["model"]
    net = Net.from_model(model)
    r = trace.reduce(trace.extract(recorded(), [0]))
    window = loops.Window(start=0.0, end=r.window_s)
    window.requests = [loops.Request(i, 0.0, 0.0, done=0.0, status="ok")
                       for i in range(64 * len(r.steps))]
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    ctx = SimpleNamespace(trace=r, window=window, net=net, chips=1, peak=peak)
    mfu = readers.step_mfu(ctx)
    expect = (100.0 * counts.dense_flops_per_image(net) * 64 * len(r.steps)
              / (r.busy_s * 197e12))
    assert mfu == pytest.approx(expect) and 0 < mfu <= 100
    window.end = 10 * r.window_s          # a slower host leaves it unchanged
    assert readers.step_mfu(ctx) == pytest.approx(mfu)
    ctx.trace = None                      # an untraced run has nothing to read
    assert readers.step_mfu(ctx) is None
