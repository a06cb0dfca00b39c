"""Clean twin: the split is a bit mask, exact under any contraction
(kernels/agg_reduce.py idiom); small odd multipliers are not splits."""
import jax
import jax.numpy as jnp


def split(x):
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    hi = jax.lax.bitcast_convert_type(xi & -4096, jnp.float32)
    return hi, x - hi


def triple(x):
    return x * 3 + x * 1024
