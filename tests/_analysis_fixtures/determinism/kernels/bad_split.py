"""Seeded Veltkamp splits: exact only when the product is rounded before
the subtraction, which FMA contraction does not guarantee."""
import jax.numpy as jnp


def split(x):
    c = x * 4097.0                                  # det-fma-split
    hi = c - (c - x)
    return hi, x - hi


def split27(x):
    c = x * ((1 << 27) + 1)                         # det-fma-split
    return c - (c - x)
