"""Seeded random weights: the key of a seed and the draw every family's
``make`` uses (``bench/families/<family>.py``).

The benchmark, not the program, makes the weights: the program is handed
them, and the reference makes the same ones again from the same seed with
the same function, so it takes nothing the program made.  A family makes
them on the device in one jitted call, in the type they are served in
(the configuration's dtype), in the program's layout: layer parameters
stacked on a leading axis.

Scales keep activations of order one at any width: matrices are normal
with standard deviation ``fan_in ** -0.5``, norm weights ``1 + 0.1 N(0, 1)``
and biases ``0.1 N(0, 1)``, so that the norms and the biases change the
result and a path that drops them shows.  The input embedding is the
published ``initializer_range``, 0.02, divided by the program's embedding
multiplier, so that the first layer sees embeddings of the published
scale.  (With the multiplier on top of 0.02, the input token dominates the
residual stream, greedy output repeats it, and no rounding, not even
fp8's, changes a served token.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A threefry key from any whole number (beyond 32 bits too)."""
    words = np.random.SeedSequence(seed % 2 ** 64).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _normal(key, shape, scale, dt):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

