"""Real-width compiles for a described TPU v5e: what the chip's compiler
refuses (unaligned slices, SMEM/VMEM overflow, unpartitionable kernels) fails
here, with no chip attached.

Only compiles — nothing runs, so nothing here says anything about results or
times. The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test process imports
this file. Keep all such compiles in this one file.
"""
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import vgg9_snn
from repro.core.hybrid import plan_vgg9_inference
from repro.core.tiling import round_up
from repro.models import vgg9

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described device is written to the persistent
        # cache but cannot be read back without the chip: keep it off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield topo
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def mosaic(monkeypatch):
    """The model paths read `interpret_mode()` at trace time and see this
    process's CPU backend; force Mosaic lowering, and drop traces made
    either way so none leaks into another test."""
    monkeypatch.setattr("repro.kernels.interpret_mode", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _param_shapes(cfg, sharding):
    shapes = jax.eval_shape(
        lambda: vgg9.init_vgg9(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda a: _shape(a.shape, a.dtype, sharding), shapes)


def test_spike_matmul_mapped_compiles_at_64_slot_conv1(one_chip):
    """conv1 at 64 slots: [1024, 5] occupancy and load-index maps, which as
    2-D SMEM arrays would pad to 512 KiB each and overflow the 1 MiB SMEM."""
    from repro.kernels.spike_conv.spike_conv import spike_matmul_mapped

    ks = plan_vgg9_inference(vgg9_snn.CIFAR10, 64).layer("conv1").kernel
    m = round_up(ks.m, ks.block_m)
    k = round_up(ks.k, ks.block_k)
    n = round_up(ks.n, ks.block_n)
    maps = (m // ks.block_m, k // ks.block_k)
    assert maps == (1024, 5)
    fn = functools.partial(spike_matmul_mapped, block_m=ks.block_m,
                           block_k=ks.block_k, block_n=ks.block_n,
                           interpret=False)
    compiled = jax.jit(fn).lower(
        _shape((m, k), jnp.float32, one_chip),
        _shape((k, n), jnp.float32, one_chip),
        _shape(maps, jnp.int32, one_chip),
        _shape(maps, jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_conv_lif_compiles_at_conv0_width(one_chip):
    from repro.kernels.dense_conv_lif.dense_conv_lif import dense_conv_lif

    cfg = vgg9_snn.CIFAR10
    ks = plan_vgg9_inference(cfg, 64).layer("conv0").kernel
    m, k = round_up(ks.m, ks.block_m), round_up(ks.k)
    n = round_up(ks.n, ks.block_n)
    fn = functools.partial(dense_conv_lif, num_steps=cfg.timesteps,
                           beta=cfg.beta, theta=cfg.theta,
                           block_m=ks.block_m, block_n=ks.block_n,
                           interpret=False)
    compiled = jax.jit(fn).lower(
        _shape((m, k), jnp.float32, one_chip),
        _shape((k, n), jnp.float32, one_chip),
        _shape((n,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lif_epilogue_fused_compiles_at_layer_width(one_chip):
    """conv1's epilogue at 64 slots: [T*B*H*W / T rows, C_out] per step."""
    from repro.kernels.lif_step.lif_step import lif_epilogue_fused

    cfg = vgg9_snn.CIFAR10
    rows, c = 64 * cfg.img_hw * cfg.img_hw, round_up(cfg.conv_channels[1])
    fn = functools.partial(lif_epilogue_fused, beta=cfg.beta,
                           theta=cfg.theta, block_r=256, block_c=c,
                           interpret=False)
    x = _shape((rows, c), jnp.float32, one_chip)
    compiled = jax.jit(fn).lower(
        x, x, x, _shape((1, c), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cfg", [vgg9_snn.CIFAR10, vgg9_snn.CIFAR10_INT4],
                         ids=["fp32", "int4"])
def test_fused_cifar10_graph_compiles_at_8_slots(cfg, one_chip, mosaic, smoke):
    """The graph `SNNRunner` dispatches, with its kernels as Mosaic custom
    calls: one dense_conv_lif, one spike_matmul_mapped per sparse layer."""
    plan = plan_vgg9_inference(cfg, 8)
    compiled = vgg9._infer_hybrid_fused.lower(
        _param_shapes(cfg, one_chip),
        _shape((8, cfg.img_hw, cfg.img_hw, cfg.in_ch), jnp.float32, one_chip),
        cfg=cfg, plan=plan, with_stats=True).compile()
    counts = smoke.kernel_launches(compiled.as_text())
    smoke.check_launches(counts, cfg)
    assert counts["spike_matmul_mapped"] == 6


def test_data_mesh_graph_compiles_over_four_devices(topo, mosaic, smoke):
    """64 slots split over a ('data',) mesh of the four described chips;
    every chip runs the 16-slot fused graph with replicated weights."""
    cfg = vgg9_snn.CIFAR10
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    params = _param_shapes(cfg, NamedSharding(mesh, PartitionSpec()))
    images = _shape((64, cfg.img_hw, cfg.img_hw, cfg.in_ch), jnp.float32,
                    NamedSharding(mesh, PartitionSpec("data")))
    fn = vgg9.sharded_infer_fn(params, images, cfg, mesh=mesh, axis="data",
                               plan=plan_vgg9_inference(cfg, 16),
                               with_stats=True)
    compiled = fn.lower(params, images).compile()
    smoke.check_launches(smoke.kernel_launches(compiled.as_text()), cfg)
    per_device = compiled.memory_analysis()
    assert per_device is not None


def _rate_graph(slots, one_chip):
    """The rate-coded T = 25 graph (`vgg9_snn.RATE_CIFAR10`) at ``slots``,
    compiled for one v5e: (kernel launches, bytes of temporaries,
    arguments and outputs)."""
    cfg = vgg9_snn.RATE_CIFAR10
    key = jax.eval_shape(lambda: jax.random.key(0))
    params = dict(_param_shapes(cfg, one_chip),
                  encoder=_shape(key.shape, key.dtype, one_chip))
    compiled = vgg9._infer_hybrid_fused.lower(
        params,
        _shape((slots, cfg.img_hw, cfg.img_hw, cfg.in_ch), jnp.float32, one_chip),
        cfg=cfg, plan=plan_vgg9_inference(cfg, slots), with_stats=True).compile()
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    return compiled, used


def test_rate_graph_compiles_at_the_cells_32_slots_and_fits_one_chip(
        one_chip, mosaic, smoke):
    """The rate-coded T = 25 graph at `rate_cifar10_int4.dense.offline`'s 32
    slots: conv0 is a spike_matmul_mapped layer (no dense core), and the
    program fits one v5e's 16 GB."""
    cfg = vgg9_snn.RATE_CIFAR10
    compiled, used = _rate_graph(32, one_chip)
    counts = smoke.kernel_launches(compiled.as_text())
    assert counts["dense_conv_lif"] == 0
    assert counts["spike_matmul_mapped"] == len(cfg.conv_channels)
    assert counts["lif_epilogue"] >= len(cfg.conv_channels) + 2
    assert counts["tpu_custom_call"] == sum(counts[k] for k in smoke.KERNELS)
    assert used < 0.9 * 16e9, used


def test_rate_graph_at_64_slots_does_not_fit_one_chip(one_chip, mosaic):
    """Why the rate cell takes 32 slots and not `dense.offline`'s 64: at 64
    the T = 25 graph needs more than a v5e's 15.75 GB of HBM, and the
    chip's compiler refuses it (the im2col of every layer is materialised
    in float32 at T*B*H*W rows)."""
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="Ran out of memory in memory space hbm"):
        _rate_graph(64, one_chip)
