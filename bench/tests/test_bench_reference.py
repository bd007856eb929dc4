"""The plain reference and its control.

The reference is written apart from the program; at `vgg9_snn.TINY` size
on the CPU it agrees with the program's own `vgg9_forward`. Each
configuration's control, the reference in a lower precision (three bf16
passes for float32, one for int4, whose products three passes carry to
within a float32 rounding), has to come out as not correct under the
configuration's limits: here at the published widths on 16 dense images,
on the CPU. Three passes are three: their products differ from one pass's
and from float32's."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare, spec, traffic  # noqa: E402
from bench.references import vgg9 as ref  # noqa: E402
from repro.configs import vgg9_snn  # noqa: E402
from repro.models import vgg9  # noqa: E402


def images(cfg, kinds, seed=0):
    rng = np.random.default_rng(seed)
    return np.stack([traffic.make_image(k, rng, cfg.img_hw, cfg.in_ch)
                     for k in kinds])


@pytest.mark.parametrize("cfg", [vgg9_snn.TINY, vgg9_snn.TINY_INT4],
                         ids=["fp32", "int4"])
def test_reference_agrees_with_the_programs_forward(cfg):
    net = ref.Net.from_model(dataclasses.asdict(cfg))
    params = ref.make_weights(jax.random.PRNGKey(7), net)
    x = jnp.asarray(images(cfg, ["patch", "dense", "silent", "dense"]))
    logits, spikes = ref.forward(params, x, net)
    with jax.default_matmul_precision("highest"):
        p_logits, p_counts = vgg9.vgg9_forward(params, x, cfg)
    np.testing.assert_allclose(logits, p_logits, atol=1e-6)
    assert set(spikes) == set(p_counts)
    for k in spikes:
        assert float(spikes[k].sum()) == float(p_counts[k])
    assert all(float(spikes[k].sum()) > 0 for k in spikes)


@pytest.mark.parametrize("cell,preset", [
    ("cifar10_fp32.dense.offline", vgg9_snn.CIFAR10),
    ("cifar100_int4.sparse.offline", vgg9_snn.CIFAR100_INT4)], ids=["fp32", "int4"])
def test_control_at_published_width_is_not_correct(cell, preset):
    net = ref.Net.from_model(dataclasses.asdict(preset))
    params = ref.make_weights(jax.random.PRNGKey(1), net)
    x = images(preset, ["dense"] * 16, seed=1)
    good = compare.reference_outputs(ref, params, net, x)
    config = spec.load_cell(cell).config
    low = compare.reference_outputs(ref, params, net, x, passes=config["control"])
    sound = compare.numbers(net, *good, *good)
    control = compare.numbers(net, *low, *good)
    limits = compare.limits(config)
    assert compare.verdict(dict(sound, lost=0), limits)
    assert not compare.verdict(dict(control, lost=0), limits)


def test_three_passes_are_not_one():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(64, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        full = np.asarray(ref._product(ref._dot, x, w, "highest"))
        err = {p: float(np.abs(np.asarray(jax.jit(ref._product, static_argnums=(0, 3))(
            ref._dot, x, w, p)) - full).max()) for p in ("high", "bf16")}
    assert 0 < err["high"] < err["bf16"] / 50
