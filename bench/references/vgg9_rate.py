"""Plain reference of the paper's rate-coded spiking VGG9 (arXiv:2411.15409,
section V-D and Table II).

The network, its LIF neurons, pooling, output decoding, int4 fake
quantization and precisions are those of the direct-coded reference
(`bench/references/vgg9.py`, whose helpers this file uses). What differs is
the input: rate coding turns each pixel p into a Bernoulli spike train of
rate clip(p, 0, 1), one draw per pixel per timestep, and the input conv
runs on those binary spikes at every step like any other layer.

The draws are spelled out here, image by image, so that a served answer can
be checked spike for spike:

* the weights carry the encoder's base key as their ``"encoder"`` leaf (a
  typed key, drawn from the weights' key in `make_weights`);
* image b's key is ``fold_in(base, h(b))``, with ``h`` the sum modulo 2**32
  over the image's float32 words ``w_i`` of ``f(w_i ^ f(i))``, where ``f``
  is MurmurHash3's 32-bit finalizer and ``i`` the word's position in the
  image's (H, W, C) order;
* its spikes are ``uniform(key_b, (T, H, W, C)) < clip(image, 0, 1)``.

So an image's spikes depend on the base key and the image alone, never on
the batch it is in.

Every layer's input is 0/1 spikes, so at ``"highest"`` a layer's product is
taken over the int4 weights' integer levels ``q`` and scaled once,
``(spikes * q) * scale``: sums of small integers, exact in float32 in any
order. A summation order that differs from the program's then cannot flip
a neuron, which over 25 LIF steps would carry on to most answers. Any other
``passes`` takes its product over the dequantized weights ``q * scale`` as
the direct reference does (``"bf16"``: those weights in bf16). This file
imports nothing of the program under test.
"""
from __future__ import annotations

import dataclasses
import functools
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp

from bench.spec import load_module

direct = load_module(Path(__file__).with_name("vgg9.py"))

#: folded into the weights' key to draw the encoder's base key
ENCODER_STREAM = 0x52415445


@dataclasses.dataclass(frozen=True)
class Net(direct.Net):
    @classmethod
    def from_model(cls, model: Dict) -> "Net":
        if model.get("coding") != "rate":
            raise ValueError("this reference covers rate coding only")
        names = [f.name for f in dataclasses.fields(cls)]
        fields = {k: model[k] for k in names}
        fields["stages"] = tuple(fields["stages"])
        return cls(**fields)


@functools.partial(jax.jit, static_argnames="net")
def make_weights(key, net: Net) -> Dict:
    """The direct reference's weights, plus the encoder's base key."""
    params = direct.make_weights(key, net)
    base = jax.random.fold_in(key, ENCODER_STREAM)
    params["encoder"] = jax.random.wrap_key_data(jax.random.key_data(base))
    return params


def _levels(x, bits: int):
    """Symmetric int-``bits`` levels of ``x`` and their scale (one per
    tensor): ``x`` is about ``q * scale``, ``q`` whole numbers in
    [-(2**(bits-1) - 1), 2**(bits-1) - 1]."""
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax), scale


def _finalize(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def encode(base, image, timesteps: int):
    """One image [H, W, C] -> its spikes [T, H, W, C] (0/1 float32)."""
    words = jax.lax.bitcast_convert_type(image.astype(jnp.float32),
                                         jnp.uint32).reshape(-1)
    where = _finalize(jnp.arange(words.size, dtype=jnp.uint32))
    h = jnp.sum(_finalize(words ^ where), dtype=jnp.uint32)
    u = jax.random.uniform(jax.random.fold_in(base, h),
                           (timesteps,) + image.shape, dtype=jnp.float32)
    return (u < jnp.clip(image, 0.0, 1.0)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("net", "passes"))
def forward(params: Dict, images: jax.Array, net: Net, passes: str = "highest"):
    """images [B, H, W, C] float32 -> (logits [B, num_classes], spikes per
    image per layer {layer: [B]}, summed over neurons and timesteps)."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, images, net, passes)


def _forward(params, images, net, passes):
    # one image at a time: nothing of a batch-mate reaches an image's draws
    spikes = jax.lax.map(
        lambda im: encode(params["encoder"], im, net.timesteps), images)
    spikes = jnp.moveaxis(spikes, 0, 1)                  # [T, B, H, W, C]
    params = {n: p for n, p in params.items() if n != "encoder"}
    if net.quant_bits:
        params = {n: dict(zip(("q", "scale"), _levels(p["w"], net.quant_bits)),
                          b=direct._fake_quant(p["b"], 8))
                  for n, p in params.items()}

    def product(op, x, layer):
        if "q" not in layer:
            return direct._product(op, x, layer["w"], passes)
        if passes == "highest":               # integer sums: exact
            return op(x, layer["q"]) * layer["scale"]
        return direct._product(op, x, layer["q"] * layer["scale"], passes)

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

    def step(state, x):
        new, counts = {}, {}

        def fire(name, current):
            u, s_prev = state[name]
            u = net.beta * u + current - s_prev * net.theta
            s = (u > net.theta).astype(jnp.float32)
            new[name] = jnp.stack([u, s])
            counts[name] = s.reshape(b, -1).sum(axis=1)
            return s

        i = 0
        for stage in net.stages:
            if stage == "MP":
                x = direct._pool(x)
                continue
            name = f"conv{i}"
            x = fire(name, product(direct._conv, x, params[name])
                     + params[name]["b"])
            i += 1
        x = x.reshape(b, -1)
        for name in ("fc0", "fc1"):
            x = fire(name, product(direct._dot, x, params[name])
                     + params[name]["b"])
        return new, (x, counts)

    _, (out, counts) = jax.lax.scan(step, state, spikes)
    group = net.population // net.num_classes
    pop = out.sum(0)
    logits = pop.reshape(b, net.num_classes, group).sum(-1) / (net.timesteps * group)
    return logits, {k: v.sum(0) for k, v in counts.items()}
