"""Quantization: QAT fake-quant, int4 packing, QTensor."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import (QTensor, dequantize, fake_quant, pack_int4,
                              qat_params, quant_levels, quantize_int4,
                              unpack_int4)


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    q = rng.integers(-8, 8, size=(6, 10)).astype(np.int8)
    packed = pack_int4(jnp.asarray(q))
    assert packed.shape == (6, 5)
    out = unpack_int4(packed, (6, 10))
    np.testing.assert_array_equal(np.asarray(out), q)


def test_quantize_int4_error_bound():
    """|w - dequant(quant(w))| <= scale/2 per channel."""
    rng = np.random.default_rng(1)
    w = rng.normal(size=(32, 16)).astype(np.float32)
    qt = quantize_int4(jnp.asarray(w), axis=-1)
    back = np.asarray(dequantize(qt))
    scale = np.asarray(qt.scale).reshape(1, -1)
    assert np.all(np.abs(w - back) <= scale / 2 + 1e-7)


def test_qtensor_storage_is_4bit():
    w = jnp.ones((64, 128))
    qt = quantize_int4(w)
    assert qt.packed.size == 64 * 128 // 2
    assert qt.nbytes_logical == 64 * 128 // 2


def test_fake_quant_levels():
    """int4 symmetric -> at most 15 distinct levels."""
    w = jnp.linspace(-1, 1, 1000)
    out = fake_quant(w, 4, None)
    assert len(np.unique(np.asarray(out))) <= 15


def test_quant_levels_products_over_spikes_are_exact_in_any_order():
    """fake_quant(w) is q * s bit for bit, and 0/1 spikes times the levels q,
    scaled after the sum, give the same bits in any summation order, where
    the dequantized weights do not."""
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(4096, 8)).astype(np.float32))
    q, s = quant_levels(w, 4)
    np.testing.assert_array_equal(np.asarray(q * s), np.asarray(fake_quant(w, 4, None)))
    assert set(np.unique(np.asarray(q)).tolist()) <= set(range(-7, 8))
    x = jnp.asarray((rng.random((16, 4096)) < 0.5).astype(np.float32))
    perm = rng.permutation(4096)
    with jax.default_matmul_precision("highest"):
        orders = [(x @ q) * s, (x[:, perm] @ q[perm]) * s,
                  (x[:, :2048] @ q[:2048] + x[:, 2048:] @ q[2048:]) * s]
        deq = [x @ (q * s), x[:, perm] @ (q * s)[perm]]
    for other in orders[1:]:
        np.testing.assert_array_equal(np.asarray(orders[0]), np.asarray(other))
    assert np.any(np.asarray(deq[0]) != np.asarray(deq[1]))


def test_fake_quant_ste_gradient():
    w = jnp.array([0.1, -0.5, 0.9])
    g = jax.grad(lambda w: fake_quant(w, 4, None).sum())(w)
    np.testing.assert_allclose(np.asarray(g), 1.0, atol=1e-6)  # in-range: identity


def test_fake_quant_int8_tighter_than_int4():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(100,)).astype(np.float32))
    e4 = jnp.abs(fake_quant(w, 4, None) - w).mean()
    e8 = jnp.abs(fake_quant(w, 8, None) - w).mean()
    assert e8 < e4


def test_qat_params_targets_weights_only():
    params = {"layer": {"w": jnp.linspace(-1, 1, 16), "b": jnp.linspace(-1, 1, 16),
                        "beta": jnp.asarray(0.15)}}
    out = qat_params(params, bits_w=4, bits_b=8)
    assert len(np.unique(np.asarray(out["layer"]["w"]))) <= 15
    assert len(np.unique(np.asarray(out["layer"]["b"]))) > 15  # int8: finer
    np.testing.assert_allclose(float(out["layer"]["beta"]), 0.15, rtol=1e-6)  # untouched


def test_qtensor_is_pytree():
    qt = quantize_int4(jnp.ones((8, 8)))
    leaves = jax.tree.leaves(qt)
    assert len(leaves) == 2
    out = jax.jit(lambda q: dequantize(q))(qt)
    assert out.shape == (8, 8)
