"""Data-mesh sharded SNN serving: param-tree sharding rules + multi-device
engine equivalence.

The SNN serves data-parallel: conv kernels / LIF parameters replicate while
the folded ``[T*B·H·W, K]`` batch axis shards over ``'data'``. The spec
rules are pure logic (no devices needed); the 2-device engine run executes
in a subprocess with ``XLA_FLAGS`` so the main test process keeps its
single-device view, and must be bit-identical — logits, per-request spike
counts and per-request skip rates — to the 1-device run.
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "repro.dist.compression",
    reason="distributed repro.dist package not implemented yet (ROADMAP open item)")

from repro.configs import vgg9_snn
from repro.dist import sharding as shd
from repro.models.vgg9 import init_vgg9


def _run_subprocess(code: str, n_dev: int = 2) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n_dev}",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, cwd=".",
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class _DataMesh:
    """Spec-rule stand-in for a serving data mesh (no devices needed)."""
    axis_names = ("data",)
    shape = {"data": 2}


def test_snn_param_tree_replicates():
    """Conv kernels, biases and LIF thresholds replicate on a data mesh:
    the weights ride along on every device while the batch shards."""
    mesh = _DataMesh()
    params = jax.eval_shape(lambda: init_vgg9(jax.random.PRNGKey(0), vgg9_snn.TINY))
    specs = shd.param_specs(params, mesh)
    import jax.sharding as js
    for path, spec in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, js.PartitionSpec))[0]:
        assert tuple(spec) in ((), (None,) * len(tuple(spec))), (path, spec)
    # conv kernel [3,3,cin,cout] replicates even on a model-capable mesh
    class _TP:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 2}
    spec = shd.param_spec((jax.tree_util.DictKey("conv1"), jax.tree_util.DictKey("w")),
                          jax.ShapeDtypeStruct((3, 3, 8, 12), jnp.float32), _TP())
    assert spec == js.PartitionSpec()
    # per-layer LIF threshold vector: 1-D -> replicated, mesh never consulted
    spec = shd.param_spec((jax.tree_util.DictKey("lif"), jax.tree_util.DictKey("theta")),
                          jax.ShapeDtypeStruct((12,), jnp.float32), None)
    assert spec == js.PartitionSpec()


def test_folded_batch_shards_on_data():
    """The slot batch (leading axis of images and of the folded activations)
    takes the data axis when it divides; odd batches degrade to replicated."""
    from jax.sharding import PartitionSpec as P
    mesh = _DataMesh()
    specs = shd.batch_spec(
        {"images": jax.ShapeDtypeStruct((4, 16, 16, 3), jnp.float32)}, mesh)
    assert specs["images"] == P(("data",), None, None, None)
    odd = shd.batch_spec(
        {"images": jax.ShapeDtypeStruct((3, 16, 16, 3), jnp.float32)}, mesh)
    assert odd["images"] == P()


def test_two_device_engine_bit_identical():
    """EngineCore + SNNRunner under a 2-device data mesh: logits, per-request
    spike counts and skip rates identical to the 1-device run."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import vgg9_snn
        from repro.dist.context import compute_mesh
        from repro.launch.mesh import make_data_mesh
        from repro.models.vgg9 import init_vgg9
        from repro.serve.api import EngineConfig
        from repro.serve.core import EngineCore
        from repro.serve.runners.snn import SNNRunner

        cfg = vgg9_snn.TINY
        params = init_vgg9(jax.random.PRNGKey(0), cfg)
        keys = jax.random.split(jax.random.PRNGKey(1), 6)
        imgs = [jax.random.uniform(k, (cfg.img_hw, cfg.img_hw, cfg.in_ch))
                for k in keys]
        imgs[1] = imgs[1] * 0.01     # a near-silent request: sparsity signal

        def serve(mesh):
            runner = SNNRunner(cfg, params)
            core = EngineCore(runner, EngineConfig(slots=4))
            ids = [core.submit(im) for im in imgs]
            if mesh is not None:
                with compute_mesh(mesh):
                    results = core.run_until_complete()
            else:
                results = core.run_until_complete()
            return [results[i] for i in ids]

        solo = serve(None)
        sharded = serve(make_data_mesh(2))
        for a, b in zip(solo, sharded):
            np.testing.assert_array_equal(np.asarray(a.outputs),
                                          np.asarray(b.outputs))
            assert a.stats["spike_total"] == b.stats["spike_total"]
            assert a.stats["out_spikes"] == b.stats["out_spikes"]
            assert a.stats["in_spikes"] == b.stats["in_spikes"]
            assert a.stats["skip_rate"] == b.stats["skip_rate"]
            assert a.stats["energy_j"] == b.stats["energy_j"]
        # the silent request's own-rows sparsity signal survives sharding
        silent = np.mean(list(sharded[1].stats["skip_rate"].values()))
        dense = np.mean(list(sharded[0].stats["skip_rate"].values()))
        assert silent > dense, (silent, dense)
        print("OK")
    """)
    assert "OK" in out


def test_compressed_train_step_threads_residual():
    """A compress_axis train step under shard_map on 4 devices: finite loss,
    residual state becomes non-zero (error feedback is live) and params
    come back replicated-identical across shards."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ArchConfig
        from repro.models import transformer as tf
        from repro.train.optim import adamw
        from repro.train.schedule import constant
        from repro.train.train_step import (init_train_state, make_train_step,
                                            shard_map_compressed_step,
                                            stack_error_state)

        cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=32,
                         n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
                         vocab=64, dtype="float32", remat="none",
                         q_chunk=8, kv_chunk=8)
        mesh = jax.make_mesh((4,), ("data",))
        opt = adamw(weight_decay=0.0)
        inner = make_train_step(lambda p, b: tf.train_loss(p, b, cfg), opt,
                                constant(1e-2), compress_axis="data")
        step = jax.jit(shard_map_compressed_step(inner, mesh))
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        state = stack_error_state(init_train_state(params, opt, compress=True), 4)
        batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
                 "labels": jnp.ones((8, 16), jnp.int32)}
        state2, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        err_mag = sum(float(jnp.abs(e).sum())
                      for e in jax.tree.leaves(state2["grad_err"]))
        assert err_mag > 0.0, "error feedback residual never populated"
        state3, metrics3 = step(state2, batch)
        assert np.isfinite(float(metrics3["loss"]))
        print("OK")
    """, n_dev=4)
    assert "OK" in out
