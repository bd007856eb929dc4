"""Fused event-driven inference pipeline: old vs. new serving hot path.

Compares the pre-fusion pipeline (T separate in-kernel-gated spike_conv +
lif_step launches per layer from a Python loop) against the fused pipeline
(one occupancy-mapped gated-matmul launch per spiking layer, timesteps
folded into the batch, conv-epilogue LIF, whole-graph jit). Reports:

* wall-clock per image batch for both paths,
* gated-matmul launches per spiking conv layer (fused must be <= 1, the
  seed path issues T),
* per-layer tile-skip rates of the occupancy map on a spatially sparse
  input (localized stimulus -> empty spike tiles downstream).

Emits one machine-readable JSON record (stdout line starting with
``HYBRID_PIPELINE_JSON``) plus the usual CSV rows / BENCH_results.json
entries.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import vgg9_snn
from repro.core.hybrid import plan_vgg9_inference
from repro.kernels.spike_conv import ops as sc_ops
from repro.models.vgg9 import init_vgg9, vgg9_infer_hybrid, vgg9_infer_hybrid_unfused

from .common import append_result, emit, time_fn

# Bigger than TINY so the occupancy map has enough tiles to skip, still
# CPU/interpret friendly.
CFG = dataclasses.replace(
    vgg9_snn.TINY, img_hw=32, stages=(16, 24, "MP", 32, 32, "MP"), fc_dim=64)
BATCH = 4


def _sparse_images(batch: int, hw: int) -> jnp.ndarray:
    """A localized bright stimulus: most of the field never spikes, so the
    spiking layers see spatially sparse events (the regime the paper's
    sparse cores — and the occupancy map — are built for)."""
    rng = np.random.default_rng(0)
    imgs = np.zeros((batch, hw, hw, 3), np.float32)
    imgs[:, : hw // 4, : hw // 4, :] = rng.uniform(
        0.5, 1.0, size=(batch, hw // 4, hw // 4, 3)).astype(np.float32)
    return jnp.asarray(imgs)


def run() -> dict:
    params = init_vgg9(jax.random.PRNGKey(0), CFG)
    imgs = _sparse_images(BATCH, CFG.img_hw)
    plan = plan_vgg9_inference(CFG, BATCH)
    n_spiking = sum(1 for l in plan.layers
                    if l.kernel is not None and l.kernel.kernel == "spike_conv_mapped")

    # --- launches per traced forward (what the executed graph dispatches).
    # Counters increment at trace time, so force a fresh trace: a warm jit
    # cache would read as zero launches.
    jax.clear_caches()
    sc_ops.reset_launch_counts()
    _, _, stats = vgg9_infer_hybrid(params, imgs, CFG, plan=plan,
                                    return_stats=True)
    fused_launches = sc_ops.launch_counts().get("spike_matmul_mapped", 0)

    sc_ops.reset_launch_counts()
    vgg9_infer_hybrid_unfused(params, imgs, CFG)
    unfused_launches = sc_ops.launch_counts().get("spike_matmul", 0)

    skip_rates = {k: float(v["skip_rate"]) for k, v in stats.items()
                  if "skip_rate" in v}

    # --- wall clock. NOTE: off a TPU the kernels run in the Pallas
    # interpreter, so there absolute times are a correctness harness, not a
    # perf signal — the TPU-relevant perf metrics are the launch counts and
    # the tile-skip rates (work the MXU never sees).
    fused_fn = lambda: vgg9_infer_hybrid(params, imgs, CFG, plan=plan)
    unfused_fn = lambda: vgg9_infer_hybrid_unfused(params, imgs, CFG)
    fused_us = time_fn(fused_fn, iters=3, warmup=1)
    unfused_us = time_fn(unfused_fn, iters=3, warmup=1)

    record = {
        "name": "hybrid_pipeline",
        "timesteps": CFG.timesteps,
        "batch": BATCH,
        "spiking_conv_layers": n_spiking,
        "launches_fused": fused_launches,
        "launches_unfused": unfused_launches,
        "launches_per_layer_fused": fused_launches / max(n_spiking, 1),
        "launches_per_layer_unfused": unfused_launches / max(n_spiking, 1),
        "skip_rates": skip_rates,
        "max_skip_rate": max(skip_rates.values()),
        "min_skip_rate": min(skip_rates.values()),
        "interpret_fused_us": round(fused_us, 1),
        "interpret_unfused_us": round(unfused_us, 1),
    }
    print("HYBRID_PIPELINE_JSON " + json.dumps(record, sort_keys=True))
    append_result(record)

    emit("hybrid_pipeline_fused", fused_us,
         f"launches/layer={record['launches_per_layer_fused']:.0f} "
         f"max_skip={record['max_skip_rate']:.2f}")
    emit("hybrid_pipeline_unfused", unfused_us,
         f"launches/layer={record['launches_per_layer_unfused']:.0f}")
    return record


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
