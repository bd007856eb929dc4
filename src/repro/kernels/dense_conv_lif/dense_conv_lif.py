"""Dense-core kernel: input-layer convolution fused with LIF over T timesteps.

TPU adaptation of the paper's weight-stationary dense core (27-PE systolic
array for the 3-channel, 3x3-filter input layer). On TPU the weight matrix
[K=27(pad), N=C_out] stays resident in VMEM across the whole M grid
(weight-stationary <=> block residency), the im2col'd image patches stream
through the MXU, and the LIF dynamics for all T timesteps are fused into the
epilogue.

Direct coding presents the *same* image every timestep, so the convolution is
computed once and the T-step LIF recurrence runs on the in-register current:
    u[t+1] = beta * u[t] + I - s[t-1] * theta ;  s[t] = u[t+1] > theta
(paper Eq. 1-2). This hoisting is bit-exact vs. per-timestep recompute and is
one of the beyond-paper wins recorded in EXPERIMENTS.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import F32_DOT


def _dense_conv_lif_kernel(x_ref, w_ref, b_ref, s_ref, u_ref, *, num_steps, beta, theta):
    """Grid step (i, j): currents = x[i] @ w[:, j] + bias[j]; run T LIF steps."""
    current = jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32,
        precision=F32_DOT,
    ) + b_ref[...]

    u = jnp.zeros_like(current)
    s = jnp.zeros_like(current)
    for t in range(num_steps):  # T is small (2-8) and static: unrolled
        u = beta * u + current - s * theta
        s = (u > theta).astype(current.dtype)
        s_ref[t, ...] = s
    u_ref[...] = u


def dense_conv_lif(
    patches: jax.Array,
    weights: jax.Array,
    bias: jax.Array,
    *,
    num_steps: int,
    beta: float,
    theta: float,
    block_m: int = 256,
    block_n: int = 128,
    interpret: bool = False,
):
    """[M, K] patches x [K, N] weights (+bias [N]) -> spikes [T, M, N], u [M, N].

    K is the full (padded) im2col depth — a single K block, since the input
    layer has K = 27 (3 channels x 3x3 filter), the same observation that
    sized the paper's 27-PE array.
    """
    m, k = patches.shape
    k2, n = weights.shape
    assert k == k2 and m % block_m == 0 and n % block_n == 0
    grid = (m // block_m, n // block_n)

    kernel = functools.partial(
        _dense_conv_lif_kernel, num_steps=num_steps, beta=beta, theta=theta
    )
    spikes, u = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, block_n), lambda i, j: (0, j)),   # weight-stationary
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((num_steps, block_m, block_n), lambda i, j: (0, i, j)),
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_steps, m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        interpret=interpret,
        name="dense_conv_lif",
    )(patches, weights, bias.reshape(1, n))
    return spikes, u
