"""GPipe pipeline parallelism: pipelined == sequential, grads flow."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType

from repro.train.pipeline import bubble_fraction, gpipe_apply

multi = pytest.mark.skipif(len(jax.devices()) < 8,
                           reason="needs 8 host devices")


def _layer_fn(lp, x):
    return jnp.tanh(x @ lp["w"] + lp["b"])


def _params(L, d, key):
    ks = jax.random.split(key, 2)
    return {"w": jax.random.normal(ks[0], (L, d, d)) * (d ** -0.5),
            "b": jax.random.normal(ks[1], (L, d)) * 0.1}


def _sequential(params, x_micro):
    def one(x):
        def body(c, lp):
            return _layer_fn(lp, c), None
        y, _ = jax.lax.scan(body, x, params)
        return y
    return jax.vmap(one)(x_micro)


@multi
def test_gpipe_matches_sequential():
    mesh = jax.make_mesh((4,), ("pipe",), axis_types=(AxisType.Auto,))
    L, d, M, B = 8, 16, 6, 2
    params = _params(L, d, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (M, B, d))
    want = _sequential(params, x)
    got = gpipe_apply(_layer_fn, params, x, mesh, axis="pipe")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@multi
def test_gpipe_backward_matches_sequential():
    """GPipe backward (autodiff through ppermute) == sequential grads."""
    mesh = jax.make_mesh((4,), ("pipe",), axis_types=(AxisType.Auto,))
    L, d, M, B = 4, 8, 4, 2
    params = _params(L, d, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (M, B, d))

    def loss_pipe(p):
        return jnp.sum(gpipe_apply(_layer_fn, p, x, mesh) ** 2)

    def loss_seq(p):
        return jnp.sum(_sequential(p, x) ** 2)

    g1 = jax.grad(loss_pipe)(params)
    g2 = jax.grad(loss_seq)(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_bubble_fraction():
    assert bubble_fraction(1, 4) == pytest.approx(0.75)
    assert bubble_fraction(32, 4) == pytest.approx(3 / 35)
    assert bubble_fraction(8, 1) == 0.0
