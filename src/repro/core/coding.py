"""Input coding schemes for SNNs: direct coding and rate coding (paper §I, §V-D).

Direct coding: the raw floating-point input is presented identically at every
timestep; the *first convolution layer* produces floating-point membrane
currents and its LIF layer emits the binary spikes that drive the rest of the
network. Because the input is timestep-invariant, the input-layer convolution
can be hoisted out of the timestep loop (computed once, reused T times) — the
optimized hybrid path does this; the faithful path recomputes per timestep.

Rate coding: each pixel intensity p in [0,1] becomes an independent Bernoulli
spike train with rate p (one draw per timestep). `rate_code_images` draws an
image's trains from a key that is a function of a base key and of the image's
own float32 bits (`image_hash`), so a served image gets the same spikes
whatever slot it sits in and whatever shares its batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def direct_code(x: jax.Array, num_steps: int) -> jax.Array:
    """Repeat input over T timesteps: [B, ...] -> [T, B, ...]."""
    return jnp.broadcast_to(x[None], (num_steps,) + x.shape)


def _mix32(h: jax.Array) -> jax.Array:
    """MurmurHash3's 32-bit finalizer: a bijection of uint32 words."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def image_hash(images: jax.Array) -> jax.Array:
    """[B, ...] -> [B] uint32: a hash of each image's float32 bits.

    Word i of an image contributes ``mix32(bits_i ^ mix32(i))``; the
    contributions are summed modulo 2**32. Integer arithmetic only, so the
    hash is exact and the same on every backend and in any summation order.
    """
    b = images.shape[0]
    bits = jax.lax.bitcast_convert_type(
        images.astype(jnp.float32), jnp.uint32).reshape(b, -1)
    pos = _mix32(jnp.arange(bits.shape[1], dtype=jnp.uint32))
    return jnp.sum(_mix32(bits ^ pos), axis=1, dtype=jnp.uint32)


def rate_code_images(key: jax.Array, images: jax.Array,
                     num_steps: int) -> jax.Array:
    """Per-image Bernoulli spike trains: [B, ...] -> binary [T, B, ...] float32.

    Image b's spikes are ``uniform(fold_in(key, image_hash(b)), (T,) +
    image shape) < clip(image, 0, 1)``: one draw per pixel per timestep from
    a key of the image's own, never of its slot or its batch-mates. Equal
    images get equal spike trains.
    """
    def one(h, x):
        u = jax.random.uniform(jax.random.fold_in(key, h),
                               (num_steps,) + x.shape, dtype=jnp.float32)
        return (u < jnp.clip(x, 0.0, 1.0).astype(jnp.float32)).astype(jnp.float32)

    return jnp.moveaxis(jax.vmap(one)(image_hash(images), images), 0, 1)


def spike_count(spikes: jax.Array) -> jax.Array:
    """Total number of spikes in a (binary) spike train."""
    return jnp.sum(spikes != 0)


def sparsity(spikes: jax.Array) -> jax.Array:
    """Fraction of zero entries (the event-driven skip opportunity)."""
    return 1.0 - jnp.mean((spikes != 0).astype(jnp.float32))
