"""Aggregation op tests: `masked_mean(neighbor_gather(..))`, the GraphSAGE
layer's aggregation, against plain numpy loops, and its gradient through the
gather's sorted VJP (the Pallas kernel, interpreted on the CPU; the table and
the kernel alone are tests/test_gather_vjp.py's)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from dragonfly2_tpu.ops import neighbor_agg_pallas as pk
from dragonfly2_tpu.ops.neighbor_agg import masked_mean, neighbor_gather


def _random_graph(n=100, k=7, h=33, seed=0):
    rng = np.random.default_rng(seed)
    states = rng.normal(size=(n, h)).astype(np.float32)
    neighbors = rng.integers(0, n, size=(n, k)).astype(np.int32)
    mask = (rng.random((n, k)) < 0.7).astype(np.float32)
    return jnp.asarray(states), jnp.asarray(neighbors), jnp.asarray(mask)


def _numpy_loop(h, nbr, mask, eps=1e-6):
    """Row by row, slot by slot: the mean of the masked-in neighbors' states."""
    h, nbr, mask = (np.asarray(a, np.float32) for a in (h, nbr, mask))
    out = np.zeros_like(h)
    for i in range(h.shape[0]):
        total, count = np.zeros(h.shape[1], np.float32), 0.0
        for slot, on in zip(nbr[i].astype(int), mask[i]):
            total += on * h[slot]
            count += on
        out[i] = total / (count + eps)
    return out


def test_xla_reference_masked_mean():
    h, nbr, mask = _random_graph()
    out = masked_mean(neighbor_gather(h, nbr), mask)
    # row 0 by hand
    m = np.asarray(mask[0])
    rows = np.asarray(h)[np.asarray(nbr[0])]
    want = (rows * m[:, None]).sum(0) / (m.sum() + 1e-6)
    np.testing.assert_allclose(np.asarray(out[0]), want, rtol=1e-5)


@pytest.mark.parametrize("n,k,hdim", [(100, 7, 33), (128, 16, 256), (257, 4, 64), (1, 2, 8)])
def test_gather_then_mean_matches_a_numpy_loop(n, k, hdim):
    h, nbr, mask = _random_graph(n, k, hdim)
    got = masked_mean(neighbor_gather(h, nbr), mask)
    assert got.shape == (n, hdim)
    np.testing.assert_allclose(np.asarray(got), _numpy_loop(h, nbr, mask), rtol=2e-4, atol=2e-5)


def test_fully_masked_row_is_zero():
    h, nbr, mask = _random_graph(64, 4, 16)
    mask = mask.at[3].set(0.0)
    got = masked_mean(neighbor_gather(h, nbr), mask)
    np.testing.assert_allclose(np.asarray(got[3]), np.zeros(16), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), _numpy_loop(h, nbr, mask), rtol=2e-4, atol=2e-5)


def test_duplicate_neighbors_counted():
    # node 0's neighbor list is [1, 1]: mean must equal h[1]
    h = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4))
    nbr = jnp.asarray([[1, 1], [0, 2], [0, 1]], jnp.int32)
    mask = jnp.ones((3, 2), jnp.float32)
    got = masked_mean(neighbor_gather(h, nbr), mask)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(h[1]), rtol=1e-5)


def test_bfloat16_states():
    h, nbr, mask = _random_graph(128, 8, 64)
    got = masked_mean(neighbor_gather(h.astype(jnp.bfloat16), nbr), mask)
    assert got.dtype == jnp.bfloat16
    want = _numpy_loop(h.astype(jnp.bfloat16), nbr, mask)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=3e-2, atol=3e-2)


# shapes the kernel admits that tests/test_gather_vjp.py's cases do not hold
@pytest.mark.parametrize("n,k,hdim", [(256, 16, 128), (512, 8, 256)])
def test_grad_through_the_sorted_vjp_matches_jnp_takes(n, k, hdim):
    """d/dh of the aggregation's squared sum, the gather's VJP taken by the
    kernel over the placed table, against the one XLA derives from `jnp.take`."""
    h, nbr, mask = _random_graph(n, k, hdim)
    h = h.astype(jnp.bfloat16)
    table = pk.edges_by_destination(np.asarray(nbr), hdim, h.dtype)
    assert table is not None

    def loss(hh, by_dst):
        return jnp.sum(masked_mean(neighbor_gather(hh, nbr, by_dst), mask).astype(jnp.float32) ** 2)

    with pltpu.force_tpu_interpret_mode():
        got = jax.grad(loss)(h, jax.tree.map(jnp.asarray, table))
    want = np.asarray(jax.grad(loss)(h.astype(jnp.float32), None))  # the derived VJP, summed in float32
    assert got.dtype == jnp.bfloat16
    # bfloat16 means and one rounding of the float32-accumulated sums
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=2.0 ** -7, atol=2.0 ** -7 * np.abs(want).max())
