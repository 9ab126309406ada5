"""Adam and the learning-rate decay that train the store's model."""

import jax
import jax.numpy as jnp
import pytest

from repro.core.optimizer import adam_init, adam_update, exponential_decay


class TestOptimizer:
    def test_adam_converges_quadratic(self):
        params = {"x": jnp.asarray(5.0), "y": jnp.asarray(-3.0)}
        opt = adam_init(params)
        for _ in range(300):
            grads = jax.grad(lambda p: p["x"] ** 2 + (p["y"] - 1) ** 2)(params)
            params, opt = adam_update(grads, opt, params, lr=0.05)
        assert abs(float(params["x"])) < 0.05
        assert abs(float(params["y"]) - 1) < 0.05

    def test_schedules(self):
        s = exponential_decay(1e-3, 0.999)
        assert float(s(jnp.asarray(0))) == pytest.approx(1e-3)
        assert float(s(jnp.asarray(100))) < 1e-3
