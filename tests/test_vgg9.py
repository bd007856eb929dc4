"""Spiking VGG9 (the paper's model): semantics, hybrid kernels, quantization."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import vgg9_snn
from repro.models.vgg9 import (VGG9Config, conv_names, init_vgg9, vgg9_forward,
                               vgg9_infer_hybrid, vgg9_loss, _maxpool_spikes)

CFG = vgg9_snn.TINY


@pytest.fixture(scope="module")
def setup():
    params = init_vgg9(jax.random.PRNGKey(0), CFG)
    imgs = jax.random.uniform(jax.random.PRNGKey(1), (4, CFG.img_hw, CFG.img_hw, 3))
    labels = jnp.array([0, 1, 2, 3])
    return params, imgs, labels


def test_forward_shapes_and_finite(setup):
    params, imgs, _ = setup
    logits, counts = vgg9_forward(params, imgs, CFG)
    assert logits.shape == (4, CFG.num_classes)
    assert bool(jnp.isfinite(logits).all())
    assert set(counts) == set(conv_names(CFG) + ["fc0", "fc1"])
    assert all(float(v) >= 0 for v in counts.values())


def test_grad_flows_through_bptt(setup):
    params, imgs, labels = setup
    loss, grads = jax.value_and_grad(vgg9_loss)(params, {"images": imgs, "labels": labels}, CFG)
    assert bool(jnp.isfinite(loss))
    g0 = float(jnp.abs(grads["conv0"]["w"]).sum())
    assert g0 > 0, "surrogate gradient must reach the input layer"


def test_hybrid_kernels_bitexact_vs_training_path(setup):
    """Dense-core + sparse-core kernel inference == pure-JAX reference."""
    params, imgs, _ = setup
    ref_logits, ref_counts = vgg9_forward(params, imgs, CFG)
    hyb_logits, hyb_counts = vgg9_infer_hybrid(params, imgs, CFG)
    np.testing.assert_array_equal(np.asarray(hyb_logits), np.asarray(ref_logits))
    for k in ref_counts:
        assert int(hyb_counts[k]) == int(ref_counts[k]), k


def test_hoisting_input_conv_is_exact(setup):
    """Direct coding: hoisted input conv == per-timestep recompute."""
    params, imgs, _ = setup
    cfg_hoist = dataclasses.replace(CFG, hoist_input_conv=True)
    cfg_slow = dataclasses.replace(CFG, hoist_input_conv=False)
    a, ca = vgg9_forward(params, imgs, cfg_hoist)
    b, cb = vgg9_forward(params, imgs, cfg_slow)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ca:
        assert int(ca[k]) == int(cb[k])


def test_int4_qat_view_changes_spikes_not_shapes(setup):
    params, imgs, _ = setup
    lq, cq = vgg9_forward(params, imgs, vgg9_snn.TINY_INT4)
    lf, cf = vgg9_forward(params, imgs, CFG)
    assert lq.shape == lf.shape
    assert int(sum(cq.values())) != int(sum(cf.values()))  # quantization moves spikes


def test_rate_coding_runs_and_spikes_scale_with_T(setup):
    params, imgs, _ = setup
    c5 = vgg9_forward(params, imgs, dataclasses.replace(CFG, coding="rate", timesteps=5),
                      rng=jax.random.PRNGKey(2))[1]
    c10 = vgg9_forward(params, imgs, dataclasses.replace(CFG, coding="rate", timesteps=10),
                       rng=jax.random.PRNGKey(2))[1]
    assert sum(float(v) for v in c10.values()) > sum(float(v) for v in c5.values())


def test_maxpool_on_spikes_is_or_gate():
    s = jnp.zeros((1, 4, 4, 1)).at[0, 0, 1, 0].set(1.0)
    out = _maxpool_spikes(s)
    assert out.shape == (1, 2, 2, 1)
    assert float(out[0, 0, 0, 0]) == 1.0     # any spike in window -> 1
    assert float(out[0, 1, 1, 0]) == 0.0
    assert set(np.unique(np.asarray(out))) <= {0.0, 1.0}


def test_population_decoding_shape():
    cfg = dataclasses.replace(CFG, population=64, num_classes=4)
    params = init_vgg9(jax.random.PRNGKey(3), cfg)
    imgs = jax.random.uniform(jax.random.PRNGKey(4), (2, cfg.img_hw, cfg.img_hw, 3))
    logits, _ = vgg9_forward(params, imgs, cfg)
    assert logits.shape == (2, 4)
