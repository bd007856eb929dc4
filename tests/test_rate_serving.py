"""The rate-coded spiking VGG9 (paper §V-D, `vgg9_snn.RATE_CIFAR10`) served
through `EngineCore` -> `SNNRunner` -> the fused graph, at TINY size: the
input layer is one more occupancy-mapped sparse layer over input spikes
that each image draws from a key of its own, so an answer does not depend
on the slot or on the batch-mates, and the training path's rate coding is
the same encoding."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import vgg9_snn
from repro.core.coding import rate_code_images
from repro.core.hybrid import plan_vgg9_inference
from repro.models.vgg9 import (_infer_hybrid_fused, init_vgg9, vgg9_forward,
                               vgg9_infer_hybrid)
from repro.serve.api import Request
from repro.serve.runners.snn import SNNRunner

CFG = dataclasses.replace(vgg9_snn.TINY_INT4, coding="rate", timesteps=5)


@pytest.fixture(scope="module")
def params():
    return dict(init_vgg9(jax.random.PRNGKey(0), CFG), encoder=jax.random.key(3))


def images(n, seed):
    return np.array(jax.random.uniform(
        jax.random.PRNGKey(seed), (n, CFG.img_hw, CFG.img_hw, CFG.in_ch)))


def serve(runner, batch):
    return runner.run([Request(i, x) for i, x in enumerate(batch)])


def assert_same_answer(a, b):
    np.testing.assert_array_equal(np.asarray(a.outputs), np.asarray(b.outputs))
    for k in ("out_spikes", "in_spikes", "skip_rate", "ts_occupancy"):
        assert a.stats[k] == b.stats[k], k


def test_an_image_is_answered_alike_in_any_slot_and_company(params):
    runner = SNNRunner(CFG, params)
    x, others = images(8, 1), images(8, 2)
    alone = serve(runner, x[5:6])[0]
    in_slot_5 = serve(runner, x)[5]
    beside_others = serve(runner, np.concatenate([others[:5], x[5:6], others[6:]]))[5]
    assert_same_answer(alone, in_slot_5)
    assert_same_answer(alone, beside_others)
    assert alone.stats["in_spikes"]["conv0"] > 0


def test_the_runner_refuses_a_rate_net_without_the_encoder_key(params):
    weights = {k: v for k, v in params.items() if k != "encoder"}
    with pytest.raises(ValueError, match="encoder"):
        SNNRunner(CFG, weights)


def test_the_input_layers_events_and_skips_reach_each_result(params):
    runner = SNNRunner(CFG, params)
    x = images(4, 4)
    x[2] = 0.0                                   # a black image never spikes
    results = serve(runner, x)
    events = rate_code_images(params["encoder"], jnp.asarray(x), CFG.timesteps)
    for i, res in enumerate(results):
        assert res.stats["in_spikes"]["conv0"] == float(events[:, i].sum())
        assert "conv0" in res.stats["skip_rate"] and "conv0" in res.stats["out_spikes"]
        assert len(res.stats["ts_occupancy"]["conv0"]) == CFG.timesteps
    assert results[2].stats["skip_rate"]["conv0"] == 1.0
    assert results[0].stats["skip_rate"]["conv0"] < 1.0
    # the energy model prices conv0 from its input events
    assert results[2].stats["energy_j"] < results[0].stats["energy_j"]


def test_the_training_paths_rate_coding_equals_the_served_graph(params):
    x = jnp.asarray(images(4, 5))
    served, served_counts = vgg9_infer_hybrid(params, x, CFG)
    with jax.default_matmul_precision("highest"):
        logits, counts = vgg9_forward(params, x, CFG, rng=params["encoder"])
        by_leaf, _ = vgg9_forward(params, x, CFG)   # the key defaults to the leaf
    np.testing.assert_allclose(np.asarray(served), np.asarray(logits), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(by_leaf), np.asarray(logits))
    assert set(counts) == set(served_counts)
    for k in counts:
        assert float(served_counts[k]) == float(counts[k]), k


def test_the_plan_puts_the_input_layer_on_the_sparse_path():
    ks = plan_vgg9_inference(CFG, 4).layer("conv0")
    assert ks.path == "sparse" and ks.kernel.kernel == "spike_conv_mapped"
    m = CFG.timesteps * 4 * CFG.img_hw ** 2
    assert (ks.kernel.m, ks.kernel.k, ks.kernel.n) == (m, 27, CFG.conv_channels[0])
    assert (ks.kernel.block_m, ks.kernel.block_k, ks.kernel.gate) == (128, 128, True)
    direct = plan_vgg9_inference(vgg9_snn.TINY_INT4, 4).layer("conv0")
    assert direct.path == "dense" and direct.kernel.kernel == "dense_conv_lif"


def test_the_encoders_device_ops_carry_the_rate_encode_scope(params):
    x = jax.ShapeDtypeStruct((2, CFG.img_hw, CFG.img_hw, CFG.in_ch), jnp.float32)
    hlo = _infer_hybrid_fused.lower(
        params, x, cfg=CFG, plan=plan_vgg9_inference(CFG, 2),
        with_stats=False).as_text(debug_info=True)
    scoped = [line for line in hlo.splitlines() if "rate_encode" in line]
    assert any("threefry" in line or "shift_right" in line for line in scoped)


def test_the_sharded_graph_on_four_devices_equals_the_unsharded_one():
    code = textwrap.dedent("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import vgg9_snn
        from repro.dist.context import compute_mesh
        from repro.launch.mesh import make_data_mesh
        from repro.models.vgg9 import init_vgg9
        from repro.serve.api import Request
        from repro.serve.runners.snn import SNNRunner

        assert len(jax.devices()) == 4
        cfg = dataclasses.replace(vgg9_snn.TINY_INT4, coding="rate", timesteps=5)
        params = dict(init_vgg9(jax.random.PRNGKey(0), cfg),
                      encoder=jax.random.key(3))
        x = np.array(jax.random.uniform(jax.random.PRNGKey(6), (8, 16, 16, 3)))
        x[3] = 0.0
        batch = [Request(i, im) for i, im in enumerate(x)]
        runner = SNNRunner(cfg, params)
        solo = runner.run(batch)
        with compute_mesh(make_data_mesh(4)):
            sharded = runner.run(batch)
        for a, b in zip(solo, sharded):
            np.testing.assert_array_equal(np.asarray(a.outputs), np.asarray(b.outputs))
            for k in ("out_spikes", "in_spikes", "skip_rate", "ts_occupancy",
                      "energy_j"):
                assert a.stats[k] == b.stats[k], k
        assert sharded[3].stats["skip_rate"]["conv0"] > sharded[0].stats["skip_rate"]["conv0"]
        print("OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK" in proc.stdout
