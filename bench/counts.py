"""Operations and bytes of the served network, counted from shapes and
spike counts so that they read the same whatever implements them.

All sizes are float32 (4 bytes), the type the serving graph moves.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

F32 = 4
LIF_OPS = 6     # per neuron per step: beta*u, +I, +bias, s*theta, -, > theta


def conv_shapes(net) -> List[Tuple[str, int, int, int]]:
    """(layer, side of its square output map, C_in, C_out) per conv layer."""
    out, hw, cin, i = [], net.img_hw, net.in_ch, 0
    for stage in net.stages:
        if stage == "MP":
            hw //= 2
            continue
        out.append((f"conv{i}", hw, cin, stage))
        cin, i = stage, i + 1
    return out


def dense_flops_per_image(net) -> float:
    """The published network's dense matmul work for one image at its T:
    conv0 once (direct coding feeds the same image at every step), every
    other layer T times, with every spike tile counted. LIF updates are
    not counted."""
    t = net.timesteps
    total = 0.0
    for name, hw, cin, cout in conv_shapes(net):
        total += 2.0 * hw * hw * 9 * cin * cout * (1 if name == "conv0" else t)
    total += 2.0 * t * (net.flat * net.fc_dim + net.fc_dim * net.population)
    return total


def dense_conv_lif_work(net, batch: int) -> Tuple[float, float]:
    """(operations, bytes) of the input layer's conv + T LIF steps over a
    batch: read the image patches (9*C_in per pixel), the weights and the
    bias; write the spikes of every step and the last membrane."""
    _, hw, cin, cout = conv_shapes(net)[0]
    m = batch * hw * hw
    t = net.timesteps
    ops = 2.0 * m * 9 * cin * cout + LIF_OPS * t * m * cout
    nbytes = F32 * (m * 9 * cin + 9 * cin * cout + cout + t * m * cout + m * cout)
    return ops, nbytes


def lif_epilogue_work(net, batch: int) -> Tuple[float, float]:
    """(operations, bytes) of every LIF epilogue launch of one batch: each
    sparse conv layer and both FC layers, once per timestep. A step over
    R x N neurons must read the currents and the N biases and write the
    spikes; the membrane and the previous spikes are the recurrence's own
    state, which the compiler may keep in on-chip memory between steps,
    so they are not counted."""
    sizes = [(batch * hw * hw, cout) for _, hw, _, cout in conv_shapes(net)[1:]]
    sizes += [(batch, net.fc_dim), (batch, net.population)]
    ops = sum(LIF_OPS * r * n for r, n in sizes) * net.timesteps
    nbytes = sum(F32 * (2 * r * n + n) for r, n in sizes) * net.timesteps
    return ops, nbytes


def spike_matmul_work(net, batch: int,
                      in_spikes: Dict[str, float]) -> Tuple[float, float]:
    """(operations, bytes) of the six sparse conv layers of one batch.

    Operations: 2 * C_out per input spike per 3x3 tap, from the spike
    counts the served results carry (summed over the batch and the T
    steps). All nine taps are counted, also those of an edge pixel that
    fall outside the output, so this is never below the useful work on
    the spikes and never above the dense count. Bytes: the output currents
    written once and the weights read once. The patches are not counted:
    a kernel that skips empty spike tiles rightly never loads them."""
    ops = nbytes = 0.0
    for name, hw, cin, cout in conv_shapes(net)[1:]:
        ops += 2.0 * cout * 9 * in_spikes[name]
        nbytes += F32 * (net.timesteps * batch * hw * hw * cout + 9 * cin * cout)
    return ops, nbytes


def mapped_tiles(net, plan, batch: int) -> Dict[str, int]:
    """(block_m x block_k) spike tiles of each sparse conv layer's im2col
    matrix under the program's plan for ``batch`` slots."""
    tiles = {}
    for name, hw, cin, _ in conv_shapes(net)[1:]:
        ks = plan.layer(name).kernel
        rows = net.timesteps * batch * hw * hw
        tiles[name] = (math.ceil(rows / ks.block_m)
                       * math.ceil(9 * cin / ks.block_k))
    return tiles


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: Dict) -> float:
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the time taken, in percent."""
    least = max(ops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
    return 100.0 * least / seconds
