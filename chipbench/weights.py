"""Random weights made from the run's seed, on the device, in one jitted
call, in the layout and type the program serves them (float32 leaves of
``Model.param_specs()``).  The benchmark makes them, so the reference can
use the same arrays without taking anything the program made."""
from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def _leaf(spec, key):
    shape, dtype = spec.shape, spec.dtype
    if len(shape) <= 1:                   # norm gains and biases
        return jnp.zeros(shape, dtype)
    return STD * jax.random.normal(key, shape, dtype)


def make(specs, seed: int):
    """Weights for the ``specs`` pytree of ShapeDtypeStructs."""
    flat, treedef = jax.tree_util.tree_flatten(specs)

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(s, k) for s, k in zip(flat, keys)])

    return jax.jit(build)(seed_key(seed))
