"""Fused element-wise LIF update kernel (VPU path).

One timestep of paper Eq. 1-2 for a whole membrane tensor:
    u' = beta * u + current - s_prev * theta ;  s = (u' > theta)
Fusing the decay, integration, soft reset, and threshold into one VMEM pass
avoids three HBM round-trips per timestep — the serving-path hot loop for
spiking layers (the training path uses the autodiff-friendly jnp version in
core.lif).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lif_step_kernel(u_ref, i_ref, s_ref, u_out_ref, s_out_ref, *, beta, theta):
    u = beta * u_ref[...] + i_ref[...] - s_ref[...] * theta
    u_out_ref[...] = u
    s_out_ref[...] = (u > theta).astype(u.dtype)


def lif_step_fused(
    u: jax.Array,
    current: jax.Array,
    prev_spike: jax.Array,
    *,
    beta: float,
    theta: float,
    block_r: int = 256,
    block_c: int = 512,
    interpret: bool = False,
):
    """u, current, prev_spike: [R, C] -> (u_next, spike). R%block_r==C%block_c==0."""
    r, c = u.shape
    assert r % block_r == 0 and c % block_c == 0, ((r, c), (block_r, block_c))
    grid = (r // block_r, c // block_c)
    spec = pl.BlockSpec((block_r, block_c), lambda i, j: (i, j))
    kernel = functools.partial(_lif_step_kernel, beta=beta, theta=theta)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), u.dtype),
            jax.ShapeDtypeStruct((r, c), u.dtype),
        ],
        interpret=interpret,
        name="lif_step",
    )(u, current, prev_spike)


# ---------------------------------------------------------------------------
# Conv-epilogue variant: bias add folded into the same VMEM pass
# ---------------------------------------------------------------------------

def _lif_epilogue_kernel(u_ref, i_ref, s_ref, b_ref, u_out_ref, s_out_ref, *, beta, theta):
    """Bias add + decay + soft reset + threshold in one pass.

    The bias is the conv/FC epilogue that the gated matmul deliberately does
    not apply (its output tiles are revisited across the k grid axis);
    folding it here means the currents take no extra HBM round-trip between
    the matmul and the LIF nonlinearity.
    """
    u = beta * u_ref[...] + (i_ref[...] + b_ref[...]) - s_ref[...] * theta
    u_out_ref[...] = u
    s_out_ref[...] = (u > theta).astype(u.dtype)


def lif_epilogue_fused(
    u: jax.Array,
    current: jax.Array,
    prev_spike: jax.Array,
    bias: jax.Array,
    *,
    beta: float,
    theta: float,
    block_r: int = 256,
    block_c: int = 512,
    interpret: bool = False,
):
    """u, current, prev_spike: [R, C]; bias: [1, C] -> (u_next, spike)."""
    r, c = u.shape
    assert bias.shape == (1, c), (bias.shape, c)
    assert r % block_r == 0 and c % block_c == 0, ((r, c), (block_r, block_c))
    grid = (r // block_r, c // block_c)
    spec = pl.BlockSpec((block_r, block_c), lambda i, j: (i, j))
    bias_spec = pl.BlockSpec((1, block_c), lambda i, j: (0, j))
    kernel = functools.partial(_lif_epilogue_kernel, beta=beta, theta=theta)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec, bias_spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), u.dtype),
            jax.ShapeDtypeStruct((r, c), u.dtype),
        ],
        interpret=interpret,
        name="lif_epilogue",
    )(u, current, prev_spike, bias)
