"""Spiking VGG9 (paper §V-A) with hybrid dense/sparse execution.

Network: 64C3-112C3-MP2-192C3-216C3-MP2-480C3-504C3-560C3-MP2-FC(1064)-FC(P)
with LIF neurons after every conv/FC layer, population-coded output (P
neurons, class score = spike count over the class's neuron group), trained
with surrogate gradients (BPTT over T timesteps) and optional int4 QAT.

Execution paths:
  * training / eval  — pure-JAX (lax.conv), autodiff-friendly; direct coding
    hoists the input conv out of the timestep scan (bit-exact, the input is
    timestep-invariant — dense-core observation from the paper).
  * hybrid inference — dense core kernel (kernels/dense_conv_lif) for the
    direct-coded input layer + occupancy-gated spike_conv kernels for the
    spiking layers; a rate-coded net's input layer is one more spiking layer
    over the encoded input spikes. Validated against the training path in
    tests.

Rate coding draws each image's input spikes from a key of its own
(`core.coding.rate_code_images`) whose base is the weights' ``"encoder"``
leaf (a typed key; `init_vgg9` leaves it out, so a training tree stays
differentiable) or, in `vgg9_forward`, its ``rng`` argument where given.

Every forward returns per-layer spike counts (the Eq. 3 workload inputs and
the Fig. 1 quantization-sparsity measurements).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..core.coding import direct_code, rate_code_images
from ..core.lif import LIFParams, lif_step
from ..core.quant import fake_quant, quant_levels


@dataclasses.dataclass(frozen=True)
class VGG9Config:
    num_classes: int = 10
    population: int = 1000          # P output neurons (paper: 1000 / 5000)
    timesteps: int = 2
    beta: float = 0.15
    theta: float = 0.5
    coding: str = "direct"          # direct | rate
    quant_bits: int = 0             # 0 = fp32, 4 = int4 QAT (biases int8)
    img_hw: int = 32
    in_ch: int = 3
    stages: Tuple = (64, 112, "MP", 192, 216, "MP", 480, 504, 560, "MP")
    fc_dim: int = 1064
    hoist_input_conv: bool = True   # beyond-paper: reuse timestep-invariant conv
    surrogate_slope: float = 25.0

    @property
    def conv_channels(self):
        return [c for c in self.stages if c != "MP"]

    @property
    def lif(self) -> LIFParams:
        return LIFParams(self.beta, self.theta, self.surrogate_slope)


def conv_names(cfg: VGG9Config):
    return [f"conv{i}" for i in range(len(cfg.conv_channels))]


def init_vgg9(key, cfg: VGG9Config, dtype=jnp.float32) -> Dict:
    params = {}
    cin = cfg.in_ch
    keys = jax.random.split(key, len(cfg.conv_channels) + 2)
    for i, cout in enumerate(cfg.conv_channels):
        fan_in = 3 * 3 * cin
        params[f"conv{i}"] = {
            "w": (jax.random.normal(keys[i], (3, 3, cin, cout)) * (2.0 / fan_in) ** 0.5).astype(dtype),
            "b": jnp.zeros((cout,), dtype),
        }
        cin = cout
    n_mp = sum(1 for s in cfg.stages if s == "MP")
    hw = cfg.img_hw // (2 ** n_mp)
    flat = hw * hw * cfg.conv_channels[-1]
    params["fc0"] = {
        "w": (jax.random.normal(keys[-2], (flat, cfg.fc_dim)) * (1.0 / flat) ** 0.5).astype(dtype),
        "b": jnp.zeros((cfg.fc_dim,), dtype),
    }
    params["fc1"] = {
        "w": (jax.random.normal(keys[-1], (cfg.fc_dim, cfg.population)) * (1.0 / cfg.fc_dim) ** 0.5).astype(dtype),
        "b": jnp.zeros((cfg.population,), dtype),
    }
    return params


#: the params leaf that holds a rate-coded net's encoder base key
ENCODER = "encoder"


def _weights(params: Dict) -> Dict:
    """The layer weights of ``params``, without the encoder key."""
    if ENCODER not in params:
        return params
    return {k: v for k, v in params.items() if k != ENCODER}


def quantized_view(params: Dict, cfg: VGG9Config) -> Dict:
    """QAT fake-quant view of the weights (paper §II-B): int-`quant_bits`
    weights, int8 biases, neuronal parameters untouched."""
    if cfg.quant_bits == 0:
        return params
    return jax.tree_util.tree_map_with_path(
        lambda path, x: fake_quant(x, cfg.quant_bits, None)
        if path[-1].key == "w" else fake_quant(x, 8, None),
        params)


def _conv(x, p):
    return jax.lax.conv_general_dilated(
        x, p["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]


def _maxpool_spikes(s):
    """2x2 max-pool on binary spikes == OR gate over the window (paper §IV-B)."""
    return jax.lax.reduce_window(s, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def vgg9_forward(params: Dict, images: jax.Array, cfg: VGG9Config, *,
                 rng: jax.Array | None = None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """images [B,H,W,C] -> (logits [B,num_classes], spike counts per layer).

    BPTT-ready: the timestep loop is a lax.scan carrying membrane potentials
    and previous spikes for every LIF layer. Rate coding draws the input
    spikes from ``rng``, else from the weights' ``"encoder"`` leaf.
    """
    qp = quantized_view(_weights(params), cfg)
    lif = cfg.lif
    names = conv_names(cfg) + ["fc0", "fc1"]
    b = images.shape[0]

    # layer output shapes (for state init)
    shapes = {}
    hw = cfg.img_hw
    stage_of = []
    ci = 0
    for s in cfg.stages:
        if s == "MP":
            hw //= 2
            stage_of.append(("MP", None))
        else:
            shapes[f"conv{ci}"] = (b, hw, hw, s)
            stage_of.append(("conv", ci))
            ci += 1
    shapes["fc0"] = (b, cfg.fc_dim)
    shapes["fc1"] = (b, cfg.population)

    def zeros_state():
        return {n: (jnp.zeros(shapes[n], jnp.float32), jnp.zeros(shapes[n], jnp.float32))
                for n in names}

    if cfg.coding == "direct":
        if cfg.hoist_input_conv:
            input_current = _conv(images, qp["conv0"])   # computed once, reused T times
            currents_in = jnp.broadcast_to(input_current[None],
                                           (cfg.timesteps,) + input_current.shape)
        else:
            coded = direct_code(images, cfg.timesteps)
            currents_in = jax.vmap(lambda im: _conv(im, qp["conv0"]))(coded)
    else:  # rate coding: binary input spikes, conv0 acts as a sparse layer
        if rng is None:
            rng = params.get(ENCODER)
        assert rng is not None, "rate coding needs an rng key or an 'encoder' leaf"
        coded = rate_code_images(rng, images, cfg.timesteps)
        currents_in = jax.vmap(lambda sp: _conv(sp, qp["conv0"]))(coded)

    def timestep(carry, current0):
        state = carry
        new_state = {}
        counts = {}

        def fire(name, current):
            u, s_prev = state[name]
            u_next, s = lif_step(u, current, s_prev, lif)
            new_state[name] = (u_next, s)
            counts[name] = jnp.sum(s)
            return s

        s = fire("conv0", current0)
        ci = 1
        for kind, idx in stage_of:
            if kind == "MP":
                s = _maxpool_spikes(s)
            elif idx is not None and idx > 0:
                s = fire(f"conv{idx}", _conv(s, qp[f"conv{idx}"]))
        s = s.reshape(b, -1)
        s = fire("fc0", s @ qp["fc0"]["w"] + qp["fc0"]["b"])
        s_out = fire("fc1", s @ qp["fc1"]["w"] + qp["fc1"]["b"])
        return new_state, (s_out, counts)

    _, (out_spikes, counts) = jax.lax.scan(timestep, zeros_state(), currents_in)
    # population decoding: class score = total spikes in the class's group
    group = cfg.population // cfg.num_classes
    pop = out_spikes.sum(0)                                  # [B, P] spike counts over T
    logits = pop.reshape(b, cfg.num_classes, group).sum(-1) / (cfg.timesteps * group)
    total_counts = {k: counts[k].sum(0) for k in counts}  # scan stacked over T
    return logits, total_counts


def vgg9_loss(params: Dict, batch: Dict, cfg: VGG9Config, *, rng=None) -> jax.Array:
    logits, _ = vgg9_forward(params, batch["images"], cfg, rng=rng)
    labels = batch["labels"]
    logits = logits * 10.0  # population rates are in [0,1]; sharpen for CE
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# Hybrid kernel inference path (dense core + sparse cores)
# ---------------------------------------------------------------------------

def _stage_plan(cfg: VGG9Config):
    """[('MP', None) | ('conv', idx>0), ...] — the post-input-layer walk."""
    plan = []
    ci = 0
    for s in cfg.stages:
        if s == "MP":
            plan.append(("MP", None))
        else:
            if ci > 0:
                plan.append(("conv", ci))
            ci += 1
    return plan


@functools.partial(jax.jit, static_argnames=("cfg", "plan", "with_stats"))
def _infer_hybrid_fused(params: Dict, images: jax.Array, *, cfg: VGG9Config,
                        plan, with_stats: bool):
    """The fused serving graph. See vgg9_infer_hybrid for the contract.
    with_stats is static: the no-stats trace returns an empty stats dict, so
    XLA drops the occupancy/row maps and per-image reductions entirely.
    Whether the kernels lower through Mosaic or run in the Pallas
    interpreter is read from the backend at trace time."""
    from ..kernels import interpret_mode
    from ..kernels.dense_conv_lif.ops import input_layer_conv_lif
    from ..kernels.lif_step.ops import lif_epilogue
    from ..kernels.spike_conv.ops import spike_conv2d_mapped

    interpret = interpret_mode()
    qp = quantized_view(_weights(params), cfg)
    b = images.shape[0]
    t = cfg.timesteps
    # stats carry per-layer tile-skip measurements *and* per-request spike
    # counts ([B] vectors) so the serving engine can split the folded batch's
    # counters back out per request. Spikes are 0/1 floats, so the per-image
    # sums recombine exactly to the scalar `counts`.
    counts: Dict[str, jax.Array] = {}
    stats: Dict[str, Dict[str, jax.Array]] = {}

    if cfg.coding == "rate":
        # Input spikes drawn per image; conv0 is a sparse layer over them.
        with jax.named_scope("rate_encode"):
            spikes = rate_code_images(params[ENCODER], images, t)
        layers = [("conv", 0)] + _stage_plan(cfg)
    else:
        # Dense core: input layer, conv once + T fused LIF steps (one launch).
        ks0 = plan.layer("conv0").kernel
        spikes, _ = input_layer_conv_lif(
            images, qp["conv0"]["w"], qp["conv0"]["b"],
            num_steps=t, beta=cfg.beta, theta=cfg.theta,
            block_m=ks0.block_m, block_n=ks0.block_n, interpret=interpret)
        counts["conv0"] = jnp.sum(spikes)
        if with_stats:
            stats["conv0"] = {"out_spikes_per_image": spikes.sum(axis=(0, 2, 3, 4))}
        layers = _stage_plan(cfg)

    # A rate-coded int net's products are 0/1 spikes times the weights'
    # integer levels, summed exactly in any order, then scaled once: its
    # currents do not depend on the kernels' tiling, so a served answer is
    # the reference's bit for bit even through T = 25 chaotic LIF steps.
    exact = cfg.coding == "rate" and cfg.quant_bits > 0

    def product_weights(name):
        """(weights the layer's product takes, scale applied after it)."""
        if not exact:
            return qp[name]["w"], None
        return quant_levels(params[name]["w"], cfg.quant_bits)

    def lif_scan_fused(cur_t, bias):
        """lax.scan of the conv-epilogue LIF over [T, rows, N] currents."""
        u0 = jnp.zeros_like(cur_t[0])

        def step(carry, cur):
            u, s_prev = carry
            u, s = lif_epilogue(u, cur, s_prev, bias, beta=cfg.beta,
                                theta=cfg.theta, interpret=interpret)
            return (u, s), s

        _, s_seq = jax.lax.scan(step, (u0, jnp.zeros_like(u0)), cur_t)
        return s_seq                                     # [T, rows, N]

    # Sparse cores: timesteps folded into the batch — ONE occupancy-mapped
    # gated matmul launch per layer, then the sequential LIF recurrence.
    x = spikes.reshape((t * b,) + spikes.shape[2:])      # [T*B, H, W, C]
    for kind, idx in layers:
        if kind == "MP":
            x = _maxpool_spikes(x)
            continue
        name = f"conv{idx}"
        ks = plan.layer(name).kernel
        w, scale = product_weights(name)
        cur, st = spike_conv2d_mapped(
            x, w,
            block_m=ks.block_m, block_k=ks.block_k, block_n=ks.block_n,
            gate=ks.gate, interpret=interpret)           # [T*B, H, W, Cout]
        if scale is not None:
            cur = cur * scale
        _, h, w, cout = cur.shape
        s_seq = lif_scan_fused(cur.reshape(t, b * h * w, cout), qp[name]["b"])
        counts[name] = jnp.sum(s_seq)
        if with_stats:
            stats[name] = dict(
                st,
                in_spikes_per_image=x.reshape(t, b, -1).sum(axis=(0, 2)),  # Eq. 3 S
                out_spikes_per_image=s_seq.reshape(t, b, -1).sum(axis=(0, 2)),
            )
        x = s_seq.reshape(t * b, h, w, cout)

    # FC layers (sparse cores with URAM weights in the paper): same folding.
    flat = x.reshape(t * b, -1)
    for name in ("fc0", "fc1"):
        w2d, scale = product_weights(name)
        in_per_image = flat.reshape(t, b, -1).sum(axis=(0, 2))
        # one launch, bias in the epilogue; float32 like the kernels (XLA's
        # default on a TPU would round the weights to bf16)
        cur = jnp.dot(flat, w2d, precision=jax.lax.Precision.HIGHEST)
        if scale is not None:
            cur = cur * scale
        s_seq = lif_scan_fused(cur.reshape(t, b, w2d.shape[-1]), qp[name]["b"])
        counts[name] = jnp.sum(s_seq)
        if with_stats:
            stats[name] = {
                "in_spikes_per_image": in_per_image,
                "out_spikes_per_image": s_seq.sum(axis=(0, 2)),
            }
        flat = s_seq.reshape(t * b, -1)

    group = cfg.population // cfg.num_classes
    pop = s_seq.sum(0)                                   # [B, P] spike counts over T
    logits = pop.reshape(b, cfg.num_classes, group).sum(-1) / (t * group)
    return logits, counts, stats


def vgg9_infer_hybrid(params: Dict, images: jax.Array, cfg: VGG9Config, *,
                      plan=None, return_stats: bool = False):
    """Fused inference via the TPU kernels: dense_conv_lif for a
    direct-coded input layer, occupancy-mapped spike_conv + conv-epilogue
    LIF for the spiking layers (a rate-coded net's conv0 among them, over
    the input spikes `rate_code_images` draws from ``params["encoder"]``).
    The whole graph is one jit (static `cfg`/`plan` hashing), with
    timesteps folded into the batch so every spiking layer issues a single
    gated-matmul launch instead of T.

    Numerics match vgg9_forward (tests assert).
    Returns (logits, counts); with return_stats=True additionally returns the
    per-layer stats: tile-skip measurements (occupancy map included) of the
    occupancy-mapped kernels plus per-image input/output spike counts for
    every layer — the quantities the serving engine splits back out per
    request.
    """
    if plan is None:
        from ..core.hybrid import plan_vgg9_inference
        plan = plan_vgg9_inference(cfg, images.shape[0])
    logits, counts, stats = _infer_hybrid_fused(
        params, images, cfg=cfg, plan=plan, with_stats=return_stats)
    if return_stats:
        return logits, counts, stats
    return logits, counts


_SHARDED_FNS: Dict = {}


def sharded_infer_fn(params, images, cfg: VGG9Config, *, mesh, axis: str,
                     plan, with_stats: bool):
    """The jitted ``shard_map`` callable behind `vgg9_infer_hybrid_sharded`
    for this batch shape: ``fn(params, images) -> (logits, counts, stats)``.

    ``params`` and ``images`` may be arrays or `jax.ShapeDtypeStruct`s (only
    their shapes are read), so the graph can be lowered for a described
    device mesh. Cached per (cfg, plan, mesh, axis, with_stats, shape)."""
    from jax.sharding import PartitionSpec as P

    ndev = int(mesh.shape[axis])
    b_local = images.shape[0] // ndev
    key = (cfg, plan, mesh, axis, with_stats, images.shape, str(images.dtype))
    if key not in _SHARDED_FNS:
        def local_fn(p, im):
            logits, counts, stats = _infer_hybrid_fused(
                p, im, cfg=cfg, plan=plan, with_stats=with_stats)
            counts = {k: v.reshape(1) for k, v in counts.items()}
            stats = {
                name: {k: (v if k.endswith("_per_image") else v[None])
                       for k, v in st.items()}
                for name, st in stats.items()}
            return logits, counts, stats

        param_shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        shape_local = jax.ShapeDtypeStruct((b_local,) + images.shape[1:],
                                           images.dtype)
        out_shapes = jax.eval_shape(local_fn, param_shapes, shape_local)
        out_specs = jax.tree.map(lambda _: P(axis), out_shapes)
        _SHARDED_FNS[key] = jax.jit(jax.shard_map(
            local_fn, mesh=mesh, in_specs=(P(), P(axis)),
            out_specs=out_specs, check_vma=False))
    return _SHARDED_FNS[key]


def vgg9_infer_hybrid_sharded(params: Dict, images: jax.Array, cfg: VGG9Config, *,
                              mesh, axis: str = "data", plan=None,
                              return_stats: bool = False):
    """Data-mesh sharded fused inference: the folded ``[T*B·H·W, K]`` spiking
    matmuls split over ``mesh``'s ``axis`` via ``shard_map``.

    Every layer of the fused graph is row-independent over the batch, so the
    global batch shards contiguously: device ``d`` serves images
    ``[d*B/ndev, (d+1)*B/ndev)`` with a *local* plan sized to ``B/ndev``
    slots, weights replicated. Logits are bit-identical to the unsharded
    graph (same per-row accumulation order; the plan only re-tiles M).

    Stat layout differs from `vgg9_infer_hybrid` so per-shard counters stay
    attributable (see `serve.runners.snn` for the consumer):

    * ``counts``  — per-layer ``[ndev]`` vectors (sum for the global count);
    * ``*_per_image`` stats — global ``[B]`` vectors (shard-concatenated);
    * every other stat leaf (``occ_map``, ``row_occ``, ``skip_rate``,
      ``block_m``, ``rows``, tile counts) — stacked with a leading ``[ndev]``
      device axis; ``row_occ[d]`` rows are in device ``d``'s local folded
      order.

    Args:
        mesh: a mesh whose ``axis`` divides the batch (``B % ndev == 0``).
        plan: optional `HybridPlan` sized to the *local* batch ``B/ndev``.
    """
    b = images.shape[0]
    ndev = int(mesh.shape[axis])
    assert b % ndev == 0, f"batch {b} must divide the '{axis}' axis ({ndev})"
    b_local = b // ndev
    if plan is None:
        from ..core.hybrid import plan_vgg9_inference
        plan = plan_vgg9_inference(cfg, b_local)

    fn = sharded_infer_fn(params, images, cfg, mesh=mesh, axis=axis,
                          plan=plan, with_stats=return_stats)
    logits, counts, stats = fn(params, images)
    if return_stats:
        return logits, counts, stats
    return logits, counts


def vgg9_infer_hybrid_unfused(params: Dict, images: jax.Array,
                              cfg: VGG9Config) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The pre-fusion pipeline: T separate in-kernel-gated spike_conv +
    lif_step launches per layer from a Python loop. Kept as the benchmark
    baseline for benchmarks/hybrid_pipeline.py."""
    from ..kernels import interpret_mode
    from ..kernels.dense_conv_lif.ops import input_layer_conv_lif
    from ..kernels.spike_conv.ops import spike_conv2d
    from ..kernels.lif_step.ops import lif_update

    assert cfg.coding == "direct"
    interpret = interpret_mode()
    qp = quantized_view(params, cfg)
    b = images.shape[0]

    # Dense core: input layer, conv once + T fused LIF steps
    spikes, _ = input_layer_conv_lif(
        images, qp["conv0"]["w"], qp["conv0"]["b"],
        num_steps=cfg.timesteps, beta=cfg.beta, theta=cfg.theta, interpret=interpret)
    counts = {"conv0": jnp.sum(spikes)}

    layer_in = spikes                                       # [T, B, H, W, C]
    for kind, idx in _stage_plan(cfg):
        if kind == "MP":
            layer_in = jax.vmap(_maxpool_spikes)(layer_in)
            continue
        name = f"conv{idx}"
        u = jnp.zeros(layer_in.shape[1:-1] + (qp[name]["w"].shape[-1],), jnp.float32)
        s_prev = jnp.zeros_like(u)
        outs = []
        for t in range(cfg.timesteps):
            cur = spike_conv2d(layer_in[t], qp[name]["w"], interpret=interpret) + qp[name]["b"]
            u, s_prev = lif_update(u, cur, s_prev, beta=cfg.beta, theta=cfg.theta,
                                   interpret=interpret)
            outs.append(s_prev)
        layer_in = jnp.stack(outs)
        counts[name] = jnp.sum(layer_in)

    # FC layers (sparse cores with URAM weights in the paper)
    flat = layer_in.reshape(cfg.timesteps, b, -1)
    for name in ("fc0", "fc1"):
        u = jnp.zeros((b, qp[name]["w"].shape[-1]), jnp.float32)
        s_prev = jnp.zeros_like(u)
        outs = []
        for t in range(cfg.timesteps):
            cur = jnp.dot(flat[t], qp[name]["w"],
                          precision=jax.lax.Precision.HIGHEST) + qp[name]["b"]
            u, s_prev = lif_update(u, cur, s_prev, beta=cfg.beta, theta=cfg.theta,
                                   interpret=interpret)
            outs.append(s_prev)
        flat = jnp.stack(outs)
        counts[name] = jnp.sum(flat)

    group = cfg.population // cfg.num_classes
    pop = flat.sum(0)
    logits = pop.reshape(b, cfg.num_classes, group).sum(-1) / (cfg.timesteps * group)
    return logits, counts
