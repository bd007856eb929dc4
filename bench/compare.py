"""Whether what the timed path produced is correct: every answer the
window's requests got, against the plain reference run once the window has
closed.

Numbers compared, each against its limit. The limits are the
configuration's own (``limits`` in ``bench/configs/<config>.json``), set
from chip readings of sound runs and of the configuration's control
(``control``: the reference in three bf16 passes for float32, in one for
int4 weights); the readings are in PERF.md:

``spike_count_gap``  for each spiking layer (the dense core conv0, the
    sparse cores conv1..conv6, fc0 and fc1), the sum over the answers of
    |served - reference| spike count, over the reference's total; the worst
    layer. Served and reference sum float32 products in different orders,
    so a neuron within rounding of the threshold may flip and carry a few
    flips downstream; a lower precision flips hundreds per image.
``answers_off``  the share of answers whose class scores differ from the
    reference's by half an output spike or more in any class (a score is a
    spike count over ``T * group``).
``silent_layers``  spiking layers that never fired over the answers: a
    silent network compares equal trivially.
``lost``  requests admitted whose answer never came or came with another
    status than ``ok``.

The largest class-score gap (``logit_gap_spikes``) is printed beside them
and not compared: a single flip's cascade moves it nearly as far as the
control does (PERF.md).
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

NUMBERS = ("spike_count_gap", "answers_off", "silent_layers", "lost")
BLOCK = 64          # reference batch


def reference_outputs(reference, params, net, images: np.ndarray,
                      passes: str = "highest"):
    """The reference over ``images`` in blocks: (logits [n, C],
    {layer: spikes [n]})."""
    logits, spikes = [], {k: [] for k in net.layers}
    for b in range(0, len(images), BLOCK):
        block = images[b:b + BLOCK]
        pad = BLOCK - len(block)      # one compiled block size
        if pad:
            block = np.concatenate([block, np.zeros((pad,) + block.shape[1:],
                                                    block.dtype)])
        lg, ct = reference.forward(params, jnp.asarray(block), net, passes)
        keep = BLOCK - pad
        logits.append(np.asarray(lg)[:keep])
        for k in net.layers:
            spikes[k].append(np.asarray(ct[k])[:keep])
    return (np.concatenate(logits),
            {k: np.concatenate(v) for k, v in spikes.items()})


def numbers(net, served_logits: np.ndarray, served_spikes: Dict[str, np.ndarray],
            ref_logits: np.ndarray, ref_spikes: Dict[str, np.ndarray]) -> Dict:
    group = net.population // net.num_classes
    gap = np.abs(served_logits - ref_logits).max(axis=1) * net.timesteps * group
    layer_gaps = {k: float(np.abs(served_spikes[k] - ref_spikes[k]).sum())
                  / max(float(ref_spikes[k].sum()), 1.0) for k in net.layers}
    silent = sum(1 for k in net.layers if ref_spikes[k].sum() <= 0)
    return {"spike_count_gap": max(layer_gaps.values()),
            "answers_off": float(np.mean(gap >= 0.5)),
            "silent_layers": silent,
            "logit_gap_spikes": float(gap.max()),
            "layer_gaps": layer_gaps}


def check(reference, params, net, traffic, requests) -> Dict:
    """The compared numbers of a run (with ``lost``)."""
    sample = [r for r in requests if r.status == "ok"]
    images = np.stack([traffic.image(r.index) for r in sample])
    ref_logits, ref_spikes = reference_outputs(reference, params, net, images)
    served_logits = np.stack([r.logits for r in sample])
    served_spikes = {k: np.array([r.spikes[k] for r in sample])
                     for k in net.layers}
    out = numbers(net, served_logits, served_spikes, ref_logits, ref_spikes)
    out["lost"] = sum(1 for r in requests if r.status not in ("ok", "refused"))
    out["compared"] = len(sample)
    return out


def limits(config: Dict) -> Dict:
    """The configuration's limit for each compared number."""
    out = config["limits"]
    if set(out) != set(NUMBERS):
        raise KeyError(f"limits must name exactly {NUMBERS}, not {sorted(out)}")
    return {k: out[k] for k in NUMBERS}


def verdict(nums: Dict, limits: Dict) -> bool:
    return all(nums[k] <= limits[k] for k in limits)


def lines(nums: Dict, limits: Dict) -> List[str]:
    return [f"check {k} {nums[k]!r} limit {limits[k]!r}" for k in limits]
