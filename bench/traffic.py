"""The one traffic generator: images and arrivals from a mix's parameters.

A mix (``bench/traffic/<name>.json``) holds:

``mode``        ``"offline"`` (the queue is kept topped up, every step
                serves a full batch) or ``"online"`` (an open loop: each
                request is due at a time drawn from the seed);
``slots``       the engine's slot count (the batch width it compiles);
``max_queue``   the engine's admission bound;
``scheduler``   the engine's batch-composition policy;
``kinds``       image kinds and their weights, e.g. ``{"silent": 1,
                "dense": 1}``;
``pool``        offline: how many distinct images the stream cycles through;
``rate_per_s``  online: mean arrivals per second (Poisson).

Every seed gets the same work in another order: the kinds are dealt in
exact proportion and shuffled, and the online gaps are the same set of
exponential quantiles, shuffled. Only the pixels of each image differ.

Image kinds (copied from the program's `chip_smoke.make_images`, the
yardstick's own copy): ``silent`` is uniform noise scaled by 0.02, so the
input layer barely fires and every sparse layer sees empty spike tiles;
``patch`` is black but for a bright top-left quarter (0.5 + 0.5 noise), so
every layer spikes in a corner and skips elsewhere; ``dense`` is uniform
noise, so every spike tile is occupied.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

KINDS = ("silent", "patch", "dense")


def make_image(kind: str, rng: np.random.Generator, hw: int,
               ch: int) -> np.ndarray:
    img = rng.uniform(size=(hw, hw, ch)).astype(np.float32)
    if kind == "silent":
        return img * np.float32(0.02)
    if kind == "patch":
        q = hw // 4
        patch = np.zeros_like(img)
        patch[:q, :q] = 0.5 + 0.5 * img[:q, :q]
        return patch
    if kind == "dense":
        return img
    raise ValueError(f"unknown image kind {kind!r}; kinds: {KINDS}")


def deal(weights: Dict[str, float], n: int) -> List[str]:
    """n kinds in the exact proportion of ``weights`` (largest remainder),
    in a fixed order."""
    names = sorted(weights)
    total = float(sum(weights[k] for k in names))
    share = [weights[k] * n / total for k in names]
    counts = [int(s) for s in share]
    for i in sorted(range(len(names)), key=lambda i: counts[i] - share[i])[
            :n - sum(counts)]:
        counts[i] += 1
    return [k for k, c in zip(names, counts) for _ in range(c)]


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """n gaps whose empirical law is exponential with mean 1/rate: the
    (i + 1/2)/n quantiles, in increasing order."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


@dataclasses.dataclass
class Traffic:
    """The requests of one run: images (cycled offline), their kinds and,
    online, the due time of each request from the window's start."""
    images: np.ndarray          # [n, hw, hw, ch] float32
    kinds: List[str]
    due_s: np.ndarray           # [n] seconds, online; empty offline

    def image(self, i: int) -> np.ndarray:
        return self.images[i % len(self.images)]

    def kind(self, i: int) -> str:
        return self.kinds[i % len(self.kinds)]


def generate(mix: Dict, seed: int, seconds: float, hw: int,
             ch: int) -> Traffic:
    rng = np.random.default_rng(seed)
    if mix["mode"] == "online":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
        gaps = rng.permutation(exponential_gaps(n, mix["rate_per_s"]))
        due = np.cumsum(gaps)
    elif mix["mode"] == "offline":
        n = int(mix["pool"])
        due = np.zeros(0)
    else:
        raise ValueError(f"unknown traffic mode {mix['mode']!r}")
    kinds = [str(k) for k in rng.permutation(deal(mix["kinds"], n))]
    images = np.stack([make_image(k, rng, hw, ch) for k in kinds])
    return Traffic(images=images, kinds=kinds, due_s=due)


def warmup_images(mix: Dict, n: int, hw: int, ch: int) -> np.ndarray:
    """Set-up's images: the mix's kinds, drawn from a fixed generator."""
    rng = np.random.default_rng(0)
    kinds = deal(mix["kinds"], n)
    return np.stack([make_image(k, rng, hw, ch) for k in kinds])
