"""Direct vs rate coding (paper §I, §V-D)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coding import (direct_code, image_hash, rate_code_images,
                               sparsity, spike_count)


def test_direct_code_repeats_input():
    x = jax.random.uniform(jax.random.PRNGKey(0), (2, 4, 4, 3))
    coded = direct_code(x, 3)
    assert coded.shape == (3, 2, 4, 4, 3)
    for t in range(3):
        np.testing.assert_array_equal(np.asarray(coded[t]), np.asarray(x))


def test_rate_code_is_binary_with_matching_rate():
    x = jnp.full((1, 32, 32, 3), 0.3)
    spikes = rate_code_images(jax.random.PRNGKey(0), x, 200)
    assert set(np.unique(np.asarray(spikes))) <= {0.0, 1.0}
    rate = float(spikes.mean())
    assert abs(rate - 0.3) < 0.02


def test_rate_code_extremes():
    x = jnp.stack([jnp.zeros((4, 4)), jnp.ones((4, 4))])
    spikes = rate_code_images(jax.random.PRNGKey(1), x, 10)
    assert float(spikes[:, 0].sum()) == 0.0
    assert float(spikes[:, 1].mean()) == 1.0


def test_spike_count_and_sparsity():
    s = jnp.array([[1.0, 0, 0, 0], [0, 1.0, 0, 0]])
    assert int(spike_count(s)) == 2
    np.testing.assert_allclose(float(sparsity(s)), 0.75)


def _mix32(h):
    h = h ^ (h >> 16)
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h = h ^ (h >> 13)
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    return h ^ (h >> 16)


def test_image_hash_is_the_spelled_out_integer_hash():
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(2), (3, 4, 4, 3)))
    words = x.astype(np.float32).view(np.uint32).reshape(3, -1).astype(np.uint64)
    pos = _mix32(np.arange(words.shape[1], dtype=np.uint64))
    expect = _mix32(words ^ pos).sum(axis=1) % 2 ** 32
    np.testing.assert_array_equal(np.asarray(image_hash(jnp.asarray(x))), expect)


def test_rate_code_images_draws_per_image_not_per_batch():
    key = jax.random.key(5)
    x = jax.random.uniform(jax.random.PRNGKey(3), (4, 8, 8, 3))
    y = jax.random.uniform(jax.random.PRNGKey(4), (4, 8, 8, 3))
    batch = rate_code_images(key, x, 6)
    alone = rate_code_images(key, x[2:3], 6)
    mixed = rate_code_images(key, jnp.stack([y[0], x[2], y[1]]), 6)
    np.testing.assert_array_equal(np.asarray(batch[:, 2]), np.asarray(alone[:, 0]))
    np.testing.assert_array_equal(np.asarray(batch[:, 2]), np.asarray(mixed[:, 1]))
    # equal images get equal trains; different images, different trains
    twice = rate_code_images(key, jnp.stack([x[0], x[0], x[1]]), 6)
    np.testing.assert_array_equal(np.asarray(twice[:, 0]), np.asarray(twice[:, 1]))
    assert bool((twice[:, 0] != twice[:, 2]).any())
    # another base key, other draws
    assert bool((rate_code_images(jax.random.key(6), x, 6) != batch).any())
