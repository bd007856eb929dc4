"""Quantization support: QAT fake-quant (STE), int4 packing, W4A16 serving.

The paper quantizes weights and biases to int4 with quantization-aware
training (Jacob et al. QAT, error folded into the loss via straight-through
estimation), keeps neuronal parameters (beta, theta, membrane) in float, and
de-quantizes accumulated data for the spiking phase (paper §II-B, §IV-D).

This module provides:
  * fake_quant        — symmetric uniform fake-quantization with STE, used in
                        training (QAT) for both the SNN and LM paths.
  * quant_levels      — fake_quant's integer levels and scale, for products
                        that sum the levels exactly and scale once.
  * quantize/dequantize, pack_int4/unpack_int4 — storage-side int4 with two
    nibbles per int8 byte (HBM traffic is the TPU analogue of FPGA LUT/BRAM
    savings; see DESIGN.md §2).
  * QTensor           — a quantized parameter container (packed data + scale)
                        consumed by kernels/int4_matmul for W4A16 serving.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


def _qrange(bits: int) -> Tuple[int, int]:
    qmax = 2 ** (bits - 1) - 1
    return -qmax, qmax  # symmetric, e.g. int4 -> [-7, 7]


# ---------------------------------------------------------------------------
# QAT fake quantization (straight-through estimator)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fake_quant(w: jax.Array, bits: int = 4, axis: int | None = None) -> jax.Array:
    """Quantize-dequantize with symmetric uniform quantization.

    Forward: w -> round(w/s).clip(qmin,qmax) * s with s = max|w| / qmax
    (per-tensor, or per-channel over `axis`).
    Backward: straight-through (identity within range, zero outside).
    """
    return _fake_quant_fwd_impl(w, bits, axis)[0]


def _scale(w, bits, axis):
    _, qmax = _qrange(bits)
    if axis is None:
        amax = jnp.max(jnp.abs(w))
    else:
        amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    return jnp.maximum(amax, 1e-8) / qmax


def quant_levels(w: jax.Array, bits: int = 4, axis: int | None = None):
    """The integer levels ``q`` (integer-valued, in w's dtype) and the scale
    ``s`` of `fake_quant`, whose forward value is ``q * s``.

    A product of 0/1 spikes with ``q`` sums small integers, exactly in
    float32 and in any order while ``|sum| < 2**24``; scaling it once after
    the sum gives the same bits whatever the summation order.
    """
    qmin, qmax = _qrange(bits)
    s = _scale(w, bits, axis)
    return jnp.clip(jnp.round(w / s), qmin, qmax), s


def _fake_quant_fwd_impl(w, bits, axis):
    _, qmax = _qrange(bits)
    q, s = quant_levels(w, bits, axis)
    in_range = (jnp.abs(w) <= (qmax + 0.5) * s).astype(w.dtype)
    return q * s, in_range


def _fq_fwd(w, bits, axis):
    out, in_range = _fake_quant_fwd_impl(w, bits, axis)
    return out, in_range


def _fq_bwd(bits, axis, in_range, g):
    return (g * in_range,)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


# ---------------------------------------------------------------------------
# Storage-side quantization (serving / checkpoints)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """Packed quantized tensor: int4 values (2 per int8 byte) + fp scale.

    `shape` is the logical (unpacked) shape; packing is along the last axis,
    which must be even. Scales are per-out-channel (last axis of the logical
    weight), shaped to broadcast on dequantize.
    """

    packed: jax.Array  # int8 [..., K//2]
    scale: jax.Array   # float [..., 1] or [1, N] per-channel
    shape: tuple       # logical shape (static)
    bits: int = 4      # static

    def tree_flatten(self):
        return (self.packed, self.scale), (self.shape, self.bits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, scale = children
        shape, bits = aux
        return cls(packed, scale, shape, bits)

    @property
    def nbytes_logical(self) -> int:
        import numpy as np
        return int(np.prod(self.shape)) * self.bits // 8


def quantize_int4(w: jax.Array, axis: int | None = -1) -> QTensor:
    """Quantize to int4 (per-channel over `axis`≠packing axis) and pack."""
    qmin, qmax = _qrange(4)
    # per-channel scale over the *output* dim: reduce over all other dims.
    if axis is None:
        s = _scale(w, 4, None)
    else:
        red = tuple(i for i in range(w.ndim) if i != (axis % w.ndim))
        s = _scale(w, 4, red)
    q = jnp.clip(jnp.round(w / s), qmin, qmax).astype(jnp.int8)
    return QTensor(pack_int4(q), s.astype(jnp.float32), tuple(w.shape), 4)


def dequantize(qt: QTensor, dtype=jnp.float32) -> jax.Array:
    q = unpack_int4(qt.packed, qt.shape)
    return (q.astype(dtype) * qt.scale.astype(dtype)).reshape(qt.shape)


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int8 values in [-8,7] into int8 bytes, two nibbles per byte.

    Packing is along the last axis (must be even): out[..., i] holds
    q[..., 2i] in the low nibble and q[..., 2i+1] in the high nibble.
    """
    assert q.shape[-1] % 2 == 0, "packing axis must be even"
    lo = q[..., 0::2] & 0xF
    hi = q[..., 1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4(packed: jax.Array, shape: tuple) -> jax.Array:
    """Inverse of pack_int4; returns int8 values in [-8,7] with `shape`."""
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    # sign-extend the 4-bit values
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1).reshape(packed.shape[:-1] + (-1,))
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# Convenience: QAT treatment of a parameter pytree
# ---------------------------------------------------------------------------

def qat_params(params, bits_w: int = 4, bits_b: int = 8):
    """Apply fake-quant to every 'w*' leaf (bits_w) and 'b*' leaf (bits_b).

    Neuronal parameters (beta/theta) and norm scales are left untouched,
    matching the paper's scheme. Leaves are identified by dict key prefix.
    """

    def walk(d):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = walk(v)
            elif k.startswith("w"):
                out[k] = fake_quant(v, bits_w, None)
            elif k.startswith("b"):
                out[k] = fake_quant(v, bits_b, None)
            else:
                out[k] = v
        return out

    return walk(params)
