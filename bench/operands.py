"""Operands made on the device from the seed, in one jitted call.

The paper's distribution: each entry is zero with probability
``zeros``, scalar by scalar, and the others hold standard-normal values,
in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def device_key(seed: int, stream: int = 0):
    """A JAX key from any non-negative ``seed`` (wider than 32 bits too)."""
    state = np.random.SeedSequence([int(seed), stream]).generate_state(1)
    return jax.random.key(int(state[0]))


def sparse(key, t: int, r: int, zeros: float):
    """(t, r) float32 with entries zeroed at rate ``zeros``; traced
    inside the caller's jit."""
    k_mask, k_val = jax.random.split(key)
    keep = jax.random.uniform(k_mask, (t, r)) >= zeros
    vals = jax.random.normal(k_val, (t, r), jnp.float32)
    return jnp.where(keep, vals, jnp.float32(0))


@functools.partial(jax.jit, static_argnames=("shapes", "zeros", "dense"))
def make(key, *, shapes: tuple, zeros: float, dense: tuple = ()):
    """Sparse operands of ``shapes`` and standard-normal dense arrays of
    ``dense`` shapes, from one key, in one program."""
    keys = jax.random.split(key, len(shapes) + len(dense))
    ops = tuple(sparse(k, t, r, zeros) for k, (t, r) in zip(keys, shapes))
    rest = tuple(jax.random.normal(k, s, jnp.float32)
                 for k, s in zip(keys[len(shapes):], dense))
    return ops, rest
