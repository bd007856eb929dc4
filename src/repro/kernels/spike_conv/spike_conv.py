"""Occupancy-gated spiking convolution kernel (sparse core, paper §IV-B).

TPU adaptation of the paper's event-driven sparse core: instead of a priority
encoder popping one spike per cycle, spikes stay binary inside dense
(block_m x block_k) VMEM tiles and the kernel *skips the MXU dot for any tile
containing zero spikes* (`@pl.when`). Event granularity 1 -> tile granularity,
which is the skip granularity the TPU memory/compute hierarchy can exploit.

The convolution itself is expressed as an im2col matmul (done by ops.py):
    patches [M, K] @ weights [K, N] -> currents [M, N]
with M = B*H_out*W_out, K = KH*KW*C_in, N = C_out. Because spike activations
are binary, the dot is effectively a masked column-sum of the weights; the
MXU executes it as a matmul, and zero tiles are skipped entirely.

Accumulation is fp32 in-place in the output block across the K grid dimension
(k is the innermost, sequential grid axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import F32_DOT

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_K = 128
DEFAULT_BLOCK_N = 128


def _spike_matmul_kernel(x_ref, w_ref, o_ref, *, gate: bool):
    """One (i, j, k) grid step: o[i,j] += x[i,k] @ w[k,j], gated on occupancy."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]

    def _accumulate():
        o_ref[...] += jnp.dot(
            x, w_ref[...], preferred_element_type=jnp.float32,
            precision=F32_DOT,
        ).astype(o_ref.dtype)

    if gate:
        # Tile-level occupancy gate: the block-granular analogue of the
        # paper's per-event skipping. On TPU this saves the MXU issue and
        # the partial-sum write for all-zero spike tiles.
        has_spike = jnp.any(x != 0)
        pl.when(has_spike)(_accumulate)
    else:
        _accumulate()


def spike_matmul(
    patches: jax.Array,
    weights: jax.Array,
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_k: int = DEFAULT_BLOCK_K,
    block_n: int = DEFAULT_BLOCK_N,
    gate: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """patches [M, K] (binary spikes) @ weights [K, N] -> [M, N] fp32.

    M, K, N must be multiples of the block sizes (ops.py pads).
    """
    m, k = patches.shape
    k2, n = weights.shape
    assert k == k2, (patches.shape, weights.shape)
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0, (
        (m, k, n), (block_m, block_k, block_n))

    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_spike_matmul_kernel, gate=gate),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="spike_matmul",
    )(patches, weights)


# ---------------------------------------------------------------------------
# Occupancy-mapped variant: the gate moves out of the kernel body
# ---------------------------------------------------------------------------

def _spike_matmul_mapped_kernel(occ_ref, lidx_ref, x_ref, w_ref, o_ref, *,
                                nk: int):
    """Grid step gated by the *prefetched* occupancy map.

    `occ_ref[i * nk + kk]` decides whether this (block_m x block_k) spike
    tile contributes. The in-kernel `jnp.any` test of the plain `spike_matmul` is
    gone: empty tiles skip the MXU dot, and — because the index maps route
    their loads through `lidx_ref` (the last occupied k-tile) — the VMEM DMA
    for both the spike tile and the weight tile is elided too (Pallas skips a
    fetch whose block index equals the previous grid step's).
    """
    i = pl.program_id(0)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(occ_ref[i * nk + kk] != 0)
    def _accumulate():
        o_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32,
            precision=F32_DOT,
        ).astype(o_ref.dtype)


def spike_matmul_mapped(
    patches: jax.Array,
    weights: jax.Array,
    occupancy: jax.Array,
    load_idx: jax.Array,
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_k: int = DEFAULT_BLOCK_K,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """patches [M, K] @ weights [K, N] -> [M, N] fp32, gated by a precomputed
    [M/block_m, K/block_k] occupancy map (see ops.occupancy_map).

    `load_idx[i, kk]` must be the largest occupied k-tile index <= kk for row
    block i (0 when none) — ops.skip_load_indices computes it. It keeps the
    input/weight block index constant across runs of empty tiles so the
    pipeline issues no DMA for them.

    Both maps are scalar-prefetched into SMEM flattened to 1-D (index
    ``i * nk + kk``): SMEM pads each row of a 2-D array to 128 words, which
    at a 64-slot conv1 batch ([1024, 5] maps) would take 512 KiB per map
    and overflow the 1 MiB SMEM.
    """
    m, k = patches.shape
    k2, n = weights.shape
    assert k == k2, (patches.shape, weights.shape)
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0, (
        (m, k, n), (block_m, block_k, block_n))
    nm, nk = m // block_m, k // block_k
    assert occupancy.shape == (nm, nk) == load_idx.shape, (
        occupancy.shape, load_idx.shape, (nm, nk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nm, n // block_n, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda i, j, kk, occ, lidx: (i, lidx[i * nk + kk])),
            pl.BlockSpec((block_k, block_n),
                         lambda i, j, kk, occ, lidx: (lidx[i * nk + kk], j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, occ, lidx: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_spike_matmul_mapped_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="spike_matmul_mapped",
    )(occupancy.reshape(-1), load_idx.reshape(-1), patches, weights)
