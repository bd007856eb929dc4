"""The rate-coded cell (`rate_cifar10_int4.*`) on the CPU at TINY size
(`vgg9_snn.TINY_INT4` with rate coding at T = 5): its reference agrees
with the program's training path spike for spike, a sound run through
`EngineCore` + `SNNRunner` is correct, served sums equal the reference's
where neurons sit exactly on the threshold, a served encoder whose draws
depend on the batch is not, the bf16 control fails the configuration's limits, and
`bench/counts_rate.py` counts conv0 as worked out by hand."""
import dataclasses
import io
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare, counts, counts_rate, harness, loops, spec, traffic  # noqa: E402
from bench.references import vgg9_rate as ref  # noqa: E402
from repro.configs import vgg9_snn  # noqa: E402
from repro.models import vgg9  # noqa: E402

CELL = "rate_cifar10_int4.dense.offline"
TINY = dataclasses.replace(vgg9_snn.TINY_INT4, coding="rate", timesteps=5)


def tiny_net():
    return ref.Net.from_model(dataclasses.asdict(TINY))


def images(n, seed):
    rng = np.random.default_rng(seed)
    return np.stack([traffic.make_image("dense", rng, TINY.img_hw, TINY.in_ch)
                     for _ in range(n)])


def tiny_cell():
    cell = spec.load_cell(CELL)
    model = dataclasses.asdict(TINY)
    cell.config = dict(cell.config, model=dict(model, stages=list(model["stages"])))
    cell.traffic = dict(cell.traffic, slots=4, max_queue=8, pool=12)
    return cell


def run(cell, seed=2**31 + 11):
    out = io.StringIO()
    harness.run_cell(cell, seed, 1.0, False, time.perf_counter(),
                     require_chip=False, out=out)
    return json.loads(out.getvalue().splitlines()[-1])


def test_the_configuration_is_the_rate_preset_at_int4():
    model = spec.load_cell(CELL).model
    assert model["coding"] == "rate" and model["timesteps"] == 25
    assert model["quant_bits"] == 4
    with pytest.raises(ValueError):
        ref.Net.from_model(dict(model, coding="direct"))


def test_reference_agrees_with_the_programs_forward():
    net = tiny_net()
    params = ref.make_weights(jax.random.PRNGKey(7), net)
    x = jnp.asarray(images(4, 0))
    logits, spikes = ref.forward(params, x, net)
    with jax.default_matmul_precision("highest"):
        p_logits, p_counts = vgg9.vgg9_forward(params, x, TINY,
                                               rng=params["encoder"])
    np.testing.assert_allclose(logits, p_logits, atol=1e-6)
    assert set(spikes) == set(p_counts)
    for k in spikes:
        assert float(spikes[k].sum()) == float(p_counts[k])
        assert float(spikes[k].sum()) > 0


def test_a_sound_run_is_correct():
    result = run(tiny_cell())
    assert result["correct"] is True and result["failed"] == 0
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert checks == {"spike_count_gap": 0, "answers_off": 0,
                      "silent_layers": 0, "lost": 0}


def test_served_sums_are_exact_where_neurons_sit_on_the_threshold():
    """theta = 5 x conv0's weight scale: exact sums leave every conv0 neuron
    whose levels add to 5 on the threshold at the first step, unfired. A
    served product over the dequantized weights rounds some of them above it
    and carries the flips to every answer; summing the integer levels keeps
    served equal to the reference."""
    params = ref.make_weights(jax.random.PRNGKey(7), tiny_net())
    _, scale = ref._levels(params["conv0"]["w"], TINY.quant_bits)
    cfg = dataclasses.replace(TINY, theta=float(np.float32(5) * np.float32(scale)))
    net = ref.Net.from_model(dataclasses.asdict(cfg))
    x = images(8, 7)
    want = compare.reference_outputs(ref, params, net, x)
    logits, _, stats = vgg9.vgg9_infer_hybrid(params, jnp.asarray(x), cfg,
                                             return_stats=True)
    spikes = {k: np.asarray(stats[k]["out_spikes_per_image"]) for k in net.layers}
    nums = compare.numbers(net, np.asarray(logits), spikes, *want)
    assert nums["spike_count_gap"] == 0 and nums["answers_off"] == 0
    assert nums["silent_layers"] == 0


def _slot_keyed(key, images, num_steps):
    """Draws keyed by the slot, not by the image: the same image gets other
    spikes in another slot."""
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(images.shape[0], dtype=jnp.uint32))
    u = jax.vmap(lambda k: jax.random.uniform(
        k, (num_steps,) + images.shape[1:]))(keys)
    return jnp.moveaxis((u < jnp.clip(images, 0, 1)[:, None]).astype(jnp.float32), 0, 1)


def _one_batch_draw(key, images, num_steps):
    """One draw for the whole batch, as a batch-wide encoder makes."""
    u = jax.random.uniform(key, (num_steps,) + images.shape)
    return (u < jnp.clip(images, 0, 1)[None]).astype(jnp.float32)


@pytest.mark.parametrize("encoder", [_slot_keyed, _one_batch_draw])
def test_a_batch_dependent_encoder_is_not_correct(encoder, monkeypatch):
    jax.clear_caches()
    monkeypatch.setattr(vgg9, "rate_code_images", encoder)
    try:
        cell = tiny_cell()
        result = run(cell)
    finally:
        jax.clear_caches()
    assert result["correct"] is False
    assert result["checks"]["answers_off"]["value"] > \
        compare.limits(cell.config)["answers_off"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_bf16_control_is_not_correct(seed):
    net = tiny_net()
    params = ref.make_weights(jax.random.PRNGKey(seed), net)
    x = images(16, seed)
    good = compare.reference_outputs(ref, params, net, x)
    config = spec.load_cell(CELL).config
    assert config["control"] == "bf16"
    low = compare.reference_outputs(ref, params, net, x, passes="bf16")
    nums = compare.numbers(net, low[0], low[1], good[0], good[1])
    nums["lost"] = 0
    assert not compare.verdict(nums, compare.limits(config))


def test_input_layer_counts_by_hand():
    net = tiny_net()            # 16 x 16 x 3 in, conv0 8 out, T = 5
    t, m = 5, 2 * 16 * 16       # two slots
    ops, nbytes = counts_rate.spike_matmul_work(
        net, 2, {"conv0": 100.0, "conv1": 0.0, "conv2": 0.0, "conv3": 0.0})
    base_ops, base_bytes = counts.spike_matmul_work(
        net, 2, {"conv1": 0.0, "conv2": 0.0, "conv3": 0.0})
    assert base_ops == 0
    assert ops == 2 * 8 * 9 * 100
    assert nbytes - base_bytes == 4 * (t * m * 8 + 27 * 8)


def test_rate_mfu_counts_conv0_at_every_step():
    net = tiny_net()
    conv0 = 2.0 * 16 * 16 * 27 * 8
    assert counts_rate.dense_flops_per_image(net) == pytest.approx(
        counts.dense_flops_per_image(net) + (net.timesteps - 1) * conv0)


def test_the_input_tile_skip_share_weights_each_step():
    net = tiny_net()
    window = loops.Window(start=0.0, end=1.0)
    window.steps = [loops.Step(0, 0.0, 0.5, {}, {"conv0": 0.25, "conv1": 0.9}),
                    loops.Step(1, 0.5, 1.0, {}, {"conv0": 0.75, "conv1": 0.1})]
    ctx = SimpleNamespace(net=net, window=window, slots=4, chips=1)
    read = spec.load_module(spec.metric_file("input_tile_skip_share")).read
    assert read(ctx) == pytest.approx(0.5)
    window.steps = [loops.Step(0, 0.0, 1.0, {}, {"conv1": 0.9})]
    assert read(ctx) is None            # a direct-coded net maps no conv0


def test_the_rate_readers_count_conv0_and_read_nothing_without_it():
    net = tiny_net()
    layers = {"conv0": 50.0, "conv1": 40.0, "conv2": 30.0, "conv3": 20.0}
    window = loops.Window(start=0.0, end=1.0)
    window.steps = [loops.Step(0, 0.0, 1.0, layers, {})]
    window.requests = [loops.Request(0, 0.0, 0.0, done=0.5, status="ok")]
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    trace = SimpleNamespace(kernel_s={"spike_matmul_mapped": 1e-3,
                                      "lif_epilogue": 1e-3}, busy_s=1e-3)
    ctx = SimpleNamespace(net=net, window=window, slots=2, chips=1, peak=peak,
                          trace=trace)

    def read(name):
        return spec.load_module(spec.metric_file(name)).read(ctx)

    ops, nbytes = counts_rate.spike_matmul_work(net, 2, layers)
    assert read("rate_spike_matmul_mapped_roofline") == pytest.approx(
        counts.roofline_share(ops, nbytes, 1e-3, peak))
    assert read("rate_step_mfu") == pytest.approx(
        100.0 * counts_rate.dense_flops_per_image(net) / (1e-3 * 197e12))
    window.steps = [loops.Step(0, 0.0, 1.0, {"conv1": 40.0}, {})]
    assert read("rate_spike_matmul_mapped_roofline") is None
