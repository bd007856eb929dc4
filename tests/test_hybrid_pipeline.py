"""Fused event-driven serving pipeline: equivalence, skip stats, launches.

The fused path (occupancy-mapped one-launch-per-layer convs, conv-epilogue
LIF, whole-graph jit) must match the training-path numerics for fp32 and
int4-QAT configs, report the exact tile-skip rate for hand-built spike
tensors, and issue one gated-matmul launch per spiking layer where the
pre-fusion path issued T.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import vgg9_snn
from repro.core.hybrid import KernelSpec, plan_vgg9_inference
from repro.kernels.spike_conv import ops as sc_ops
from repro.models.vgg9 import (init_vgg9, vgg9_forward, vgg9_infer_hybrid,
                               vgg9_infer_hybrid_unfused)

CFG = vgg9_snn.TINY


@pytest.fixture(scope="module")
def setup():
    params = init_vgg9(jax.random.PRNGKey(0), CFG)
    imgs = jax.random.uniform(jax.random.PRNGKey(1), (4, CFG.img_hw, CFG.img_hw, 3))
    return params, imgs


# ---------------------------------------------------------------------------
# Equivalence: fused kernels vs the pure-JAX training path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg", [CFG, vgg9_snn.TINY_INT4], ids=["fp32", "int4"])
def test_fused_matches_training_path(setup, cfg):
    params, imgs = setup
    ref_logits, ref_counts = vgg9_forward(params, imgs, cfg)
    logits, counts = vgg9_infer_hybrid(params, imgs, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), atol=1e-5)
    for k in ref_counts:
        assert int(counts[k]) == int(ref_counts[k]), k


def test_fused_matches_unfused_bitexact(setup):
    """Folding T into the batch + occupancy mapping must not change numerics
    vs the per-timestep in-kernel-gated pipeline."""
    params, imgs = setup
    a, ca = vgg9_infer_hybrid(params, imgs, CFG)
    b, cb = vgg9_infer_hybrid_unfused(params, imgs, CFG)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ca:
        assert int(ca[k]) == int(cb[k]), k


# ---------------------------------------------------------------------------
# Occupancy map: known-empty tiles -> exact skip rate
# ---------------------------------------------------------------------------

def test_known_empty_tiles_report_expected_skip_rate():
    """Image 0 all-zero, image 1 all-one: its 256 im2col rows fill exactly
    two 128-row tiles, so the occupancy map must skip exactly half."""
    spikes = jnp.concatenate([
        jnp.zeros((1, 16, 16, 8), jnp.float32),
        jnp.ones((1, 16, 16, 8), jnp.float32),
    ])                                                   # M = 2*256 rows
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 8, 16))
    out, stats = sc_ops.spike_conv2d_mapped(spikes, w, block_m=128, interpret=True)
    assert float(stats["tiles_total"]) == 4.0            # 4 row tiles x 1 k tile
    assert float(stats["tiles_occupied"]) == 2.0
    assert float(stats["skip_rate"]) == 0.5
    # skipped tiles still produce exact zeros / correct outputs
    from repro.kernels.spike_conv.ref import conv_ref
    np.testing.assert_allclose(np.asarray(out), np.asarray(conv_ref(spikes, w)),
                               atol=1e-4)
    assert float(jnp.abs(out[0]).max()) == 0.0


def test_occupancy_map_and_load_indices():
    patches = jnp.zeros((512, 256)).at[0, 0].set(1.0).at[300, 200].set(1.0)
    occ = sc_ops.occupancy_map(patches, 256, 128)
    np.testing.assert_array_equal(np.asarray(occ), [[1, 0], [0, 1]])
    lidx = sc_ops.skip_load_indices(occ)
    # empty tiles re-point at the last occupied k tile (0 when none yet)
    np.testing.assert_array_equal(np.asarray(lidx), [[0, 0], [0, 1]])


def test_all_empty_input_skips_everything():
    spikes = jnp.zeros((1, 16, 16, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 8, 16))
    out, stats = sc_ops.spike_conv2d_mapped(spikes, w, interpret=True)
    assert float(stats["skip_rate"]) == 1.0
    assert float(jnp.abs(out).max()) == 0.0


# ---------------------------------------------------------------------------
# Launch accounting: one gated matmul per spiking layer (vs T unfused)
# ---------------------------------------------------------------------------

def test_fused_launches_once_per_spiking_layer(setup):
    params, imgs = setup
    n_spiking = len(CFG.conv_channels) - 1
    jax.clear_caches()                       # force a fresh trace to count

    sc_ops.reset_launch_counts()
    vgg9_infer_hybrid(params, imgs, CFG)
    assert sc_ops.launch_counts().get("spike_matmul_mapped", 0) == n_spiking

    sc_ops.reset_launch_counts()
    vgg9_infer_hybrid_unfused(params, imgs, CFG)
    assert sc_ops.launch_counts().get("spike_matmul", 0) == n_spiking * CFG.timesteps


# ---------------------------------------------------------------------------
# Planner: kernel/block selection drives the pipeline
# ---------------------------------------------------------------------------

def test_plan_selects_kernels_and_blocks():
    plan = plan_vgg9_inference(CFG, batch=4)
    assert plan.layer("conv0").path == "dense"
    assert plan.layer("conv0").kernel.kernel == "dense_conv_lif"
    ks = plan.layer("conv1").kernel
    assert isinstance(ks, KernelSpec) and ks.kernel == "spike_conv_mapped"
    # timesteps folded into the batch: M = T*B*H*W
    assert ks.m == CFG.timesteps * 4 * CFG.img_hw * CFG.img_hw
    assert ks.k == 9 * CFG.conv_channels[0]
    # sparse layers tile M at the MXU minimum for finest skip granularity
    assert ks.block_m == 128
    for name in ("fc0", "fc1"):
        assert plan.layer(name).kernel.kernel == "fc_lif"
    # plans are hashable (static jit arguments)
    hash(plan)


def test_fused_respects_custom_plan(setup):
    """Block-size overrides flow from the plan into the kernels unchanged."""
    params, imgs = setup
    plan = plan_vgg9_inference(CFG, batch=4)
    layers = tuple(
        dataclasses.replace(
            l, kernel=dataclasses.replace(l.kernel, block_m=256))
        if l.kernel and l.kernel.kernel == "spike_conv_mapped" else l
        for l in plan.layers)
    big = dataclasses.replace(plan, layers=layers)
    a, _ = vgg9_infer_hybrid(params, imgs, CFG, plan=big)
    ref, _ = vgg9_forward(params, imgs, CFG)
    np.testing.assert_allclose(np.asarray(a), np.asarray(ref), atol=1e-5)
