"""Paper Table III: throughput/power vs prior work + kernel-level skip rates.

FPGA side: the calibrated model reproduces our accelerator's FPS/power for
the perf^2/perf^4 configs (paper: 120 FPS @0.73 W CIFAR10-perf^2, 218 FPS
@2.35 W CIFAR100-perf^4, 51x throughput vs [7]).

TPU side: measures the *occupancy-gated* spike-conv skip opportunity (the
fraction of MXU tiles the sparse-core kernel skips at real spike densities)
and the wall-clock of the jitted hybrid inference path on this host as a
relative sanity number.
"""
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import vgg9_snn
from repro.configs.vgg9_snn import LW_ALLOCATIONS
from repro.core.energy import energy_per_image
from repro.core.sparsity import tile_occupancy
from repro.core.workload import scale_allocation
from repro.data.synthetic import image_batch
from repro.models.vgg9 import init_vgg9, vgg9_forward

from .common import emit, time_fn
from .fig4_energy import weight_bytes, workloads


def fpga_side():
    for ds, perf, paper_fps, paper_w in (("cifar10", 2, 120, 0.73),
                                         ("cifar100", 4, 218, 2.35),
                                         ("svhn", 4, 110, 0.89)):
        alloc = scale_allocation(list(LW_ALLOCATIONS[ds]), perf)
        e = energy_per_image(workloads(ds), alloc, weight_bytes(0.5), "int4")
        emit(f"table3/{ds}_perf{perf}", e["latency_s"] * 1e6,
             f"fps={e['throughput_fps']:.0f};paper_fps={paper_fps};"
             f"power_w={e['power_pipelined_w']:.2f};paper_w={paper_w}")
    # headline: 51x throughput vs [7] (4.7 FPS on CIFAR100)
    alloc = scale_allocation(list(LW_ALLOCATIONS["cifar100"]), 4)
    e = energy_per_image(workloads("cifar100"), alloc, weight_bytes(0.5), "int4")
    emit("table3/vs_prior_cifar100", 0.0,
         f"speedup_vs_4.7fps={e['throughput_fps']/4.7:.0f}x;paper=51x")


def tpu_side():
    cfg = dataclasses.replace(vgg9_snn.TINY, num_classes=4)
    params = init_vgg9(jax.random.PRNGKey(0), cfg)
    imgs = image_batch(0, 0, 32, num_classes=4, hw=cfg.img_hw)["images"]
    fwd = jax.jit(lambda im: vgg9_forward(params, im, cfg))
    us = time_fn(fwd, imgs)
    logits, counts = fwd(imgs)
    total = sum(float(v) for v in counts.values())
    emit("table3/tpu_hybrid_forward", us, f"spikes_per_batch={total:.0f}")

    # tile-skip opportunity at measured spike densities
    for density in (0.05, 0.15, 0.3):
        spikes = (jax.random.uniform(jax.random.PRNGKey(1), (64, 28 * 28 * 9)) < density)
        occ = float(tile_occupancy(spikes.astype(jnp.float32), 128))
        emit(f"table3/tile_skip_density_{density}", 0.0,
             f"occupied_frac={occ:.3f};mxu_skip_frac={1-occ:.3f}")


def run():
    fpga_side()
    tpu_side()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
