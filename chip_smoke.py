#!/usr/bin/env python3
"""Smoke check: the paper's spiking VGG9 served on a TPU with Mosaic kernels.

    python chip_smoke.py [--seed S]      # one chip
    python chip_smoke.py --chips 4       # only the data-mesh path, 4 chips

Serves `vgg9_snn.CIFAR10` and `CIFAR10_INT4` at their published widths, with
random weights made from ``--seed``, through `EngineCore` + `SNNRunner`, and
checks what comes out. The phases run in this order and each raises on
failure:

1. refuse any platform but TPU (the script prints no result elsewhere);
2. turn on the persistent compilation cache;
3. build both precisions from the seed;
4. check the three kernels against their ``ref.py`` at real layer widths:
   `input_layer_conv_lif` (conv0), `spike_conv2d_mapped` (conv1 on binary
   spikes), `lif_epilogue`;
5. compile the fused serving graph and count its kernels
   (``tpu_custom_call`` by kernel name);
6. serve near-silent, patch and dense images at 8 and 64 slots, both
   precisions;
7. compare every served result with `vgg9_forward` run on the same chip
   under ``jax.default_matmul_precision("highest")``.

With ``--chips 4`` only the data-mesh path runs: 64 slots split over a
``('data',)`` mesh of four chips, compared with the unsharded run on one
chip of the same process, and held to bit-identity.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Timings printed on earlier lines are smoke timings, not metrics.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path
from typing import Dict, List, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import vgg9_snn  # noqa: E402
from repro.core.hybrid import plan_vgg9_inference  # noqa: E402
from repro.kernels import interpret_mode  # noqa: E402
from repro.models import vgg9  # noqa: E402
from repro.serve.api import EngineConfig  # noqa: E402
from repro.serve.core import EngineCore  # noqa: E402
from repro.serve.runners.snn import SNNRunner  # noqa: E402

# --- tolerances --------------------------------------------------------------
# Kernels: both sides compute in float32, so currents and membranes (values of
# order 1) agree to a few ulps times the reduction depth (<= 576 terms).
KERNEL_ATOL = 1e-4
# LIF epilogue: the same elementwise float32 ops in the same order as the ref.
LIF_ATOL = 1e-6
# A spike may flip only where the reference membrane lies within rounding of
# theta; any larger share of flipped neurons means a less precise kernel.
MAX_FLIP_FRACTION = 1e-4
# End to end: one flipped spike can cascade through later layers, so the
# served graph is held to bounds, not equality, against vgg9_forward.
E2E_COUNT_RTOL = 1e-3       # per-layer total spike count, relative
E2E_LOGIT_ATOL = 0.05       # = 10 output spikes of one class group at T=2
E2E_TOP1_MIN = 0.95         # share of requests whose top-1 class agrees

SLOTS = (8, 64)
KINDS = ("silent", "patch", "dense")
KERNELS = ("dense_conv_lif", "spike_matmul_mapped", "lif_epilogue")


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu() -> jax.Device:
    """Phase 1: the first device must be a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}); no result")
    return dev


def build(seed: int, cfgs: Sequence) -> Dict[str, tuple]:
    """Phase 3: (cfg, params) per precision, one set of raw weights."""
    params = vgg9.init_vgg9(jax.random.PRNGKey(seed), cfgs[0])
    return {("int4" if c.quant_bits == 4 else "fp32"): (c, params)
            for c in cfgs}


def make_images(cfg, n: int, seed: int):
    """n images cycling near-silent, patch and dense (see `KINDS`)."""
    rng = np.random.default_rng(seed)
    shape = (cfg.img_hw, cfg.img_hw, cfg.in_ch)
    imgs, kinds = [], []
    for i in range(n):
        kind = KINDS[i % len(KINDS)]
        img = rng.uniform(size=shape).astype(np.float32)
        if kind == "silent":
            img *= 0.02
        elif kind == "patch":
            q = cfg.img_hw // 4
            patch = np.zeros(shape, np.float32)
            patch[:q, :q] = 0.5 + 0.5 * img[:q, :q]
            img = patch
        imgs.append(img)
        kinds.append(kind)
    return np.stack(imgs), kinds


def _flips(s_kernel, s_ref, name: str, *, stepped: bool) -> np.ndarray:
    """Neurons whose spike differs (at any step when ``stepped``: axis 0 is
    time); raises above the flip bound."""
    differ = np.asarray(s_kernel) != np.asarray(s_ref)
    sites = differ.any(axis=0) if stepped else differ
    frac = float(sites.mean())
    log(f"  {name}: flipped spike sites {int(sites.sum())}/{sites.size} "
        f"({frac:.2e}, bound {MAX_FLIP_FRACTION:.0e})")
    if frac > MAX_FLIP_FRACTION:
        raise AssertionError(f"{name}: {frac:.2e} of spike sites differ "
                             f"from the reference")
    return sites


def _within(name: str, diff: float, atol: float) -> None:
    log(f"  {name}: max |kernel - ref| = {diff:.3e} (bound {atol:.0e})")
    if not diff <= atol:
        raise AssertionError(f"{name}: {diff:.3e} exceeds {atol:.0e}")


def check_kernels(cfg, params, images) -> None:
    """Phase 4: each kernel against its ref.py on identical inputs."""
    from repro.kernels.dense_conv_lif.ops import input_layer_conv_lif
    from repro.kernels.dense_conv_lif.ref import dense_conv_lif_ref
    from repro.kernels.lif_step.ops import lif_epilogue
    from repro.kernels.lif_step.ref import lif_step_ref
    from repro.kernels.spike_conv.ops import spike_conv2d_mapped
    from repro.kernels.spike_conv.ref import conv_ref, im2col

    interpret = interpret_mode()
    t = cfg.timesteps
    b = images.shape[0]
    plan = plan_vgg9_inference(cfg, b)
    images = jnp.asarray(images)

    # conv0: the dense core, conv once + T fused LIF steps
    w0, b0 = params["conv0"]["w"], params["conv0"]["b"]
    ks = plan.layer("conv0").kernel
    spikes, u = input_layer_conv_lif(
        images, w0, b0, num_steps=t, beta=cfg.beta, theta=cfg.theta,
        block_m=ks.block_m, block_n=ks.block_n, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        s_ref, u_ref = dense_conv_lif_ref(
            im2col(images, 3, 3, "SAME"), w0.reshape(-1, w0.shape[-1]), b0,
            num_steps=t, beta=cfg.beta, theta=cfg.theta)
    s_ref = np.asarray(s_ref).reshape(spikes.shape)
    u_ref = np.asarray(u_ref).reshape(u.shape)
    log(f"kernel dense_conv_lif: conv0 {tuple(images.shape)} -> "
        f"{tuple(spikes.shape)}")
    flips = _flips(spikes, s_ref, "dense_conv_lif spikes", stepped=True)
    _within("dense_conv_lif membrane (unflipped sites)",
            float(np.abs(np.asarray(u) - u_ref)[~flips].max()), KERNEL_ATOL)

    # conv1: the sparse core on conv0's binary spikes, timesteps folded
    x = spikes.reshape((t * b,) + spikes.shape[2:])
    w1 = params["conv1"]["w"]
    ks = plan.layer("conv1").kernel
    cur, st = spike_conv2d_mapped(
        x, w1, block_m=ks.block_m, block_k=ks.block_k, block_n=ks.block_n,
        interpret=interpret)
    with jax.default_matmul_precision("highest"):
        cur_ref = conv_ref(x, w1)
    log(f"kernel spike_conv2d_mapped: conv1 {tuple(x.shape)} -> "
        f"{tuple(cur.shape)}, skip_rate {float(st['skip_rate']):.4f}")
    _within("spike_conv2d_mapped currents",
            float(jnp.abs(cur - cur_ref).max()), KERNEL_ATOL)

    # LIF epilogue at conv1's width: [T*B*H*W rows, C_out]
    rng = np.random.default_rng(1)
    shape = (int(np.prod(cur.shape[:-1])), w1.shape[-1])
    u0 = jnp.asarray(rng.normal(size=shape), jnp.float32)
    i0 = jnp.asarray(rng.normal(size=shape), jnp.float32)
    s0 = jnp.asarray(rng.uniform(size=shape) < 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(scale=0.1, size=shape[-1:]), jnp.float32)
    u1, s1 = lif_epilogue(u0, i0, s0, bias, beta=cfg.beta, theta=cfg.theta,
                          interpret=interpret)
    u1_ref, s1_ref = lif_step_ref(u0, i0 + bias, s0, beta=cfg.beta,
                                  theta=cfg.theta)
    log(f"kernel lif_epilogue: {tuple(shape)}")
    _within("lif_epilogue membrane", float(jnp.abs(u1 - u1_ref).max()),
            LIF_ATOL)
    flips = _flips(s1, s1_ref, "lif_epilogue spikes", stepped=False)
    near = np.abs(np.asarray(u1_ref) - cfg.theta) <= LIF_ATOL
    if (flips & ~near).any():
        raise AssertionError("lif_epilogue: a spike flipped away from theta")


def kernel_launches(hlo_text: str) -> Dict[str, int]:
    """``tpu_custom_call`` ops in compiled HLO text, in total and by the
    kernel name the op's metadata carries (``.../<name>/pallas_call``)."""
    counts = {"tpu_custom_call": 0, **dict.fromkeys(KERNELS, 0)}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        counts["tpu_custom_call"] += 1
        m = re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)
        name = m.group(1) if m else "unnamed"
        counts[name] = counts.get(name, 0) + 1
    return counts


def check_launches(counts: Dict[str, int], cfg) -> None:
    """One dense_conv_lif launch, one spike_matmul_mapped launch per sparse
    conv layer, at least one lif_epilogue per spiking layer (their T-step
    scans may be unrolled), and no other custom call."""
    n_sparse = len(cfg.conv_channels) - 1
    if counts["dense_conv_lif"] != 1 or counts["spike_matmul_mapped"] != n_sparse:
        raise AssertionError(f"fused graph kernels {counts}: expected 1 "
                             f"dense_conv_lif and {n_sparse} spike_matmul_mapped")
    if counts["lif_epilogue"] < n_sparse + 2:
        raise AssertionError(f"fused graph kernels {counts}: expected >= "
                             f"{n_sparse + 2} lif_epilogue")
    if counts["tpu_custom_call"] != sum(counts[k] for k in KERNELS):
        raise AssertionError(f"fused graph holds unnamed kernels: {counts}")


def compile_fused(cfg, params, images, plan):
    """Phase 5: compile the jitted graph `SNNRunner` dispatches; returns
    (compiled, seconds)."""
    t0 = time.perf_counter()
    compiled = vgg9._infer_hybrid_fused.lower(
        params, jnp.asarray(images), cfg=cfg, plan=plan,
        with_stats=True).compile()
    return compiled, time.perf_counter() - t0


def serve(cfg, params, images, slots: int):
    """Phase 6: every image through EngineCore + SNNRunner; returns
    (results in submit order, first drain seconds, one warm batch seconds).
    The warm batch re-serves the first `slots` images and must reproduce
    their results bit for bit."""
    core = EngineCore(SNNRunner(cfg, params), EngineConfig(slots=slots))
    rids = [core.submit(img) for img in images]
    t0 = time.perf_counter()
    done = core.run_until_complete()
    first_s = time.perf_counter() - t0
    results = [done[r] for r in rids]
    if any(r.status != "ok" for r in results):
        raise AssertionError(f"{slots} slots: statuses "
                             f"{sorted({r.status for r in results})}")
    again = [core.submit(img) for img in images[:slots]]
    t0 = time.perf_counter()
    done = core.run_until_complete()
    warm_s = time.perf_counter() - t0
    for rid, res in zip(again, results):
        if not np.array_equal(np.asarray(done[rid].outputs),
                              np.asarray(res.outputs)):
            raise AssertionError(f"{slots} slots: re-served logits differ")
    return results, first_s, warm_s


def compare(cfg, params, images, results) -> Dict[str, object]:
    """Phase 7: served results against vgg9_forward on the same images."""
    with jax.default_matmul_precision("highest"):
        logits, counts = jax.jit(vgg9.vgg9_forward, static_argnames="cfg")(
            params, jnp.asarray(images), cfg=cfg)
    ref_logits = np.asarray(logits)
    served = np.stack([np.asarray(r.outputs) for r in results])
    layers = list(counts)
    served_counts = {k: sum(r.stats["out_spikes"][k] for r in results)
                     for k in layers}
    ref_counts = {k: float(counts[k]) for k in layers}
    rel = {k: abs(served_counts[k] - ref_counts[k]) / max(ref_counts[k], 1.0)
           for k in layers}
    return {
        "layers": layers,
        "served_counts": served_counts,
        "ref_counts": ref_counts,
        "count_rel": rel,
        "logit_max_abs": float(np.abs(served - ref_logits).max()),
        "logits_equal": float(np.mean(np.all(served == ref_logits, axis=1))),
        "top1_agree": float(np.mean(served.argmax(1) == ref_logits.argmax(1))),
    }


def check_compare(m: Dict[str, object], tag: str) -> None:
    silent = [k for k in m["layers"] if m["served_counts"][k] <= 0]
    if silent:
        raise AssertionError(f"{tag}: layers {silent} never spiked; a silent "
                             f"network compares equal trivially")
    worst = max(m["count_rel"], key=m["count_rel"].get)
    log(f"  {tag} vs vgg9_forward: worst layer count diff {worst} "
        f"{m['count_rel'][worst]:.2e} (bound {E2E_COUNT_RTOL:.0e}); logits "
        f"max |diff| {m['logit_max_abs']:.4f} (bound {E2E_LOGIT_ATOL}); "
        f"logits equal {m['logits_equal']:.3f}; top-1 agree "
        f"{m['top1_agree']:.3f} (bound {E2E_TOP1_MIN})")
    if m["count_rel"][worst] > E2E_COUNT_RTOL:
        raise AssertionError(f"{tag}: {worst} spike count off by "
                             f"{m['count_rel'][worst]:.2e}")
    if m["logit_max_abs"] > E2E_LOGIT_ATOL:
        raise AssertionError(f"{tag}: logits off by {m['logit_max_abs']}")
    if m["top1_agree"] < E2E_TOP1_MIN:
        raise AssertionError(f"{tag}: top-1 agrees on {m['top1_agree']}")


def report_serving(tag: str, results, kinds: List[str]) -> None:
    """Per-layer spike counts and per-kind mean skip rates of a served run."""
    layers = list(results[0].stats["out_spikes"])
    counts = {k: int(sum(r.stats["out_spikes"][k] for r in results))
              for k in layers}
    log(f"  {tag} spikes per layer: {counts}")
    for kind in KINDS:
        rs = [r for r, k in zip(results, kinds) if k == kind]
        skip = {k: round(float(np.mean([r.stats["skip_rate"][k] for r in rs])), 4)
                for k in rs[0].stats["skip_rate"]}
        log(f"  {tag} skip rate ({kind}, {len(rs)} requests): {skip}")


def run_one_chip(seed: int, cfgs=(vgg9_snn.CIFAR10, vgg9_snn.CIFAR10_INT4),
                 slots: Sequence[int] = SLOTS) -> None:
    models = build(seed, cfgs)
    cfg0, params0 = models["fp32"]
    probe, _ = make_images(cfg0, slots[0], seed + 1)
    log("phase 4: kernels against ref.py")
    check_kernels(cfg0, params0, probe)

    for slot_count in slots:
        n = slot_count + slot_count // 2           # the last batch is partial
        images, kinds = make_images(cfg0, n, seed + 2)
        for prec, (cfg, params) in models.items():
            tag = f"{prec}@{slot_count}"
            log(f"phase 5-7: {tag}, {n} requests")
            plan = plan_vgg9_inference(cfg, slot_count)
            compiled, compile_s = compile_fused(
                cfg, params, images[:slot_count], plan)
            counts = kernel_launches(compiled.as_text())
            log(f"  {tag} compiled in {compile_s:.1f} s; kernels {counts}")
            check_launches(counts, cfg)
            results, first_s, warm_s = serve(cfg, params, images, slot_count)
            log(f"  {tag} served: first drain {first_s:.2f} s; one warm "
                f"batch {warm_s:.4f} s (smoke timing, not a metric)")
            report_serving(tag, results, kinds)
            check_compare(compare(cfg, params, images, results), tag)


def run_data_mesh(seed: int, ndev: int,
                  cfgs=(vgg9_snn.CIFAR10, vgg9_snn.CIFAR10_INT4),
                  slots: int = 64) -> None:
    """--chips path: the slot batch split over a ('data',) mesh, against
    the unsharded run on one chip, held to bit-identity."""
    from repro.dist.context import compute_mesh
    from repro.launch.mesh import make_data_mesh

    if len(jax.devices()) < ndev:
        raise SystemExit(f"chip_smoke: --chips {ndev} needs {ndev} devices, "
                         f"JAX found {len(jax.devices())}")
    mesh = make_data_mesh(ndev)
    for prec, (cfg, params) in build(seed, cfgs).items():
        images, kinds = make_images(cfg, slots, seed + 2)
        tag = f"{prec}@{slots}"
        one, _, _ = serve(cfg, params, images, slots)
        with compute_mesh(mesh):
            sharded, first_s, warm_s = serve(cfg, params, images, slots)
        log(f"  {tag} over {ndev} chips: first drain {first_s:.2f} s; one "
            f"warm batch {warm_s:.4f} s (smoke timing, not a metric)")
        report_serving(f"{tag} sharded", sharded, kinds)
        silent = [k for k in one[0].stats["out_spikes"]
                  if sum(r.stats["out_spikes"][k] for r in one) <= 0]
        if silent:
            raise AssertionError(f"{tag}: layers {silent} never spiked")
        for key in ("outputs", "out_spikes", "skip_rate"):
            same = all(
                np.array_equal(np.asarray(a.outputs), np.asarray(b.outputs))
                if key == "outputs" else a.stats[key] == b.stats[key]
                for a, b in zip(one, sharded))
            log(f"  {tag} sharded vs unsharded {key}: "
                f"{'bit-identical' if same else 'DIFFERENT'}")
            if not same:
                raise AssertionError(f"{tag}: sharded {key} differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the data-mesh path on four chips")
    args = ap.parse_args(argv)

    dev = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips > 1:
        run_data_mesh(args.seed, args.chips)
    else:
        run_one_chip(args.seed)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}; "
        f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
