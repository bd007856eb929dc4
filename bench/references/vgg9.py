"""Plain reference of the paper's direct-coded spiking VGG9 (arXiv:2411.15409).

Written against the paper's equations, in straightforward `jax.numpy`, and
importing nothing of the program under test:

* LIF with soft reset (Eq. 1-2): ``u <- beta*u + I - s_prev*theta``,
  ``s = u > theta``;
* direct coding: the image is the input current source at every timestep,
  so the input conv is the same at each step;
* 2x2 max-pool on binary spikes (an OR over the window);
* population-coded output: class score = spikes of the class's group of
  ``population/num_classes`` output neurons, over T, divided by
  ``T * group``;
* int4 configurations: symmetric per-tensor fake quantization, weights to
  ``quant_bits`` and biases to 8 bits (paper section II-B).

`forward` computes every matmul and convolution in float32 at one of
three precisions: ``"highest"`` (full float32 products, the precision the
configurations state), ``"high"``, three bf16 passes (hi*hi + hi*lo +
lo*hi), or ``"bf16"``, one bf16 pass, spelled out here so that they read
the same on any backend. A lower one is a configuration's control
(``control`` in its file): the step below the stated precision that a later
change might be tempted to take, and that changes the result. The split
into bf16 parts rounds with `lax.reduce_precision`: a float32 -> bf16 ->
float32 round trip may be kept at float32 by the TPU compiler, which left
``lo`` zero and made three passes one.

`make_weights` draws the weights the benchmark serves: He-normal convs,
1/fan-in FC weights and zero biases, made on the device in one jitted call.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Net:
    """The sizes the reference needs, read from a configuration file's
    ``model`` object."""
    num_classes: int
    population: int
    timesteps: int
    beta: float
    theta: float
    quant_bits: int
    img_hw: int
    in_ch: int
    stages: Tuple
    fc_dim: int

    @classmethod
    def from_model(cls, model: Dict) -> "Net":
        if model.get("coding", "direct") != "direct":
            raise ValueError("the reference covers direct coding only")
        names = [f.name for f in dataclasses.fields(cls)]
        fields = {k: model[k] for k in names}
        fields["stages"] = tuple(fields["stages"])
        return cls(**fields)

    @property
    def convs(self):
        return [c for c in self.stages if c != "MP"]

    @property
    def layers(self):
        """Every spiking layer, input to output."""
        return [f"conv{i}" for i in range(len(self.convs))] + ["fc0", "fc1"]

    @property
    def flat(self) -> int:
        pools = sum(1 for s in self.stages if s == "MP")
        return (self.img_hw // 2 ** pools) ** 2 * self.convs[-1]


@functools.partial(jax.jit, static_argnames="net")
def make_weights(key, net: Net) -> Dict:
    keys = jax.random.split(key, len(net.convs) + 2)
    params, cin = {}, net.in_ch
    for i, cout in enumerate(net.convs):
        std = (2.0 / (9 * cin)) ** 0.5
        params[f"conv{i}"] = {
            "w": jax.random.normal(keys[i], (3, 3, cin, cout)) * std,
            "b": jnp.zeros((cout,), jnp.float32)}
        cin = cout
    for j, (name, d_in, d_out) in enumerate(
            (("fc0", net.flat, net.fc_dim), ("fc1", net.fc_dim, net.population))):
        params[name] = {
            "w": jax.random.normal(keys[-2 + j], (d_in, d_out)) * (1.0 / d_in) ** 0.5,
            "b": jnp.zeros((d_out,), jnp.float32)}
    return params


def _fake_quant(x, bits: int):
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def _bf16(x):
    """``x`` rounded to bf16, as float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi.astype(jnp.bfloat16), _bf16(x - hi).astype(jnp.bfloat16)


def _product(op, x, w, passes: str):
    """``op(x, w)`` in float32: at full precision (under the caller's
    ``default_matmul_precision("highest")``), or as three or one bf16
    passes."""
    if passes == "highest":
        return op(x, w)
    f32 = {"preferred_element_type": jnp.float32}
    if passes == "bf16":
        return op(_bf16(x).astype(jnp.bfloat16), _bf16(w).astype(jnp.bfloat16), **f32)
    if passes != "high":
        raise ValueError(f"unknown precision {passes!r}")
    (xh, xl), (wh, wl) = _split(x), _split(w)
    return op(xh, wh, **f32) + op(xh, wl, **f32) + op(xl, wh, **f32)


def _conv(x, w, **kw):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"), **kw)


def _dot(x, w, **kw):
    return jnp.matmul(x, w, **kw)


def _pool(s):
    """2x2 max-pool of binary spikes: an OR over each window."""
    return jax.lax.reduce_window(s, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


@functools.partial(jax.jit, static_argnames=("net", "passes"))
def forward(params: Dict, images: jax.Array, net: Net, passes: str = "highest"):
    """images [B, H, W, C] float32 -> (logits [B, num_classes], spikes per
    image per layer {layer: [B]}, summed over neurons and timesteps).

    The timestep loop is a `lax.scan` carrying each layer's membrane and
    previous spikes."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, images, net, passes)


def _forward(params, images, net, passes):
    if net.quant_bits:
        params = {n: {"w": _fake_quant(p["w"], net.quant_bits),
                      "b": _fake_quant(p["b"], 8)} for n, p in params.items()}
    b = images.shape[0]

    state, hw, i = {}, net.img_hw, 0
    for stage in net.stages:
        if stage == "MP":
            hw //= 2
        else:
            state[f"conv{i}"] = jnp.zeros((2, b, hw, hw, stage), jnp.float32)
            i += 1
    state["fc0"] = jnp.zeros((2, b, net.fc_dim), jnp.float32)
    state["fc1"] = jnp.zeros((2, b, net.population), jnp.float32)

    # direct coding: the input current is the same at every step
    current0 = _product(_conv, images, params["conv0"]["w"], passes) + params["conv0"]["b"]

    def step(state, _):
        new, counts = {}, {}

        def fire(name, current):
            u, s_prev = state[name]
            u = net.beta * u + current - s_prev * net.theta
            s = (u > net.theta).astype(jnp.float32)
            new[name] = jnp.stack([u, s])
            counts[name] = s.reshape(b, -1).sum(axis=1)
            return s

        x, i = fire("conv0", current0), 1
        for stage in net.stages[1:]:
            if stage == "MP":
                x = _pool(x)
                continue
            name = f"conv{i}"
            x = fire(name, _product(_conv, x, params[name]["w"], passes)
                     + params[name]["b"])
            i += 1
        x = x.reshape(b, -1)
        for name in ("fc0", "fc1"):
            x = fire(name, _product(_dot, x, params[name]["w"], passes)
                     + params[name]["b"])
        return new, (x, counts)

    _, (out, counts) = jax.lax.scan(step, state, None, length=net.timesteps)
    group = net.population // net.num_classes
    pop = out.sum(0)
    logits = pop.reshape(b, net.num_classes, group).sum(-1) / (net.timesteps * group)
    return logits, {k: v.sum(0) for k, v in counts.items()}
