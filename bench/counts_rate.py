"""Operations and bytes of a rate-coded network, whose input layer
conv0 is a sparse layer like conv1..conv6: the counts of `bench.counts`
with conv0 added, counted by the same rules, from shapes and the spike
counts the served results carry.

All sizes are float32 (4 bytes), the type the serving graph moves.
"""
from __future__ import annotations

from typing import Dict, Tuple

from bench import counts


def _conv0(net):
    _, hw, cin, cout = counts.conv_shapes(net)[0]
    return hw, cin, cout


def dense_flops_per_image(net) -> float:
    """`counts.dense_flops_per_image` with conv0 counted T times: a
    rate-coded input differs at every step, so its conv is not reused."""
    hw, cin, cout = _conv0(net)
    conv0 = 2.0 * hw * hw * 9 * cin * cout
    return counts.dense_flops_per_image(net) + (net.timesteps - 1) * conv0


def spike_matmul_work(net, batch: int,
                      in_spikes: Dict[str, float]) -> Tuple[float, float]:
    """`counts.spike_matmul_work` with conv0's input events added: 2 * C_out
    per input spike per 3x3 tap; its output currents written once, its
    weights read once."""
    ops, nbytes = counts.spike_matmul_work(net, batch, in_spikes)
    hw, cin, cout = _conv0(net)
    ops += 2.0 * cout * 9 * in_spikes["conv0"]
    nbytes += counts.F32 * (net.timesteps * batch * hw * hw * cout
                            + 9 * cin * cout)
    return ops, nbytes

