"""Multinomial logistic regression: one dense layer over the flattened input.
The tests' stand-in for a configuration's plain reference (float32, highest
precision), in the layout ``flax.linen.Dense`` gives the repo's ``lr`` model."""

import jax
import jax.numpy as jnp


def forward(variables, x):
    dense = variables["params"]["Dense_0"]
    return jnp.matmul(x.reshape(x.shape[0], -1), dense["kernel"],
                      precision=jax.lax.Precision.HIGHEST) + dense["bias"]
