"""Parameter helpers (no flax — plain pytrees): initialization, and the
cast of a stored weight to the compute dtype."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def trunc_normal(rng, shape, stddev, dtype=jnp.float32):
    return stddev * jax.random.truncated_normal(rng, -2.0, 2.0, shape, dtype)


def dense_init(rng, fan_in: int, shape, dtype=jnp.float32):
    """Variance-scaling init (stddev = 1/sqrt(fan_in))."""
    return trunc_normal(rng, shape, fan_in ** -0.5, dtype)


@jax.named_scope("weights_cast")
def cast(w, dtype):
    """``w`` in the compute dtype, under the ``weights_cast`` scope, so
    the profiler's trace names the cast's device time."""
    return w.astype(dtype)


def stacked(rng, n: int, init_fn):
    """Stack n independent inits along a new leading axis (for scan)."""
    rngs = jax.random.split(rng, n)
    return jax.vmap(init_fn)(rngs)
