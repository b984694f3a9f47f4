"""Seeded weights, made on the device in one jitted call.

A family's reference module describes its parameters in the published
naming (``param_spec``): name -> (shape, mean, std); per-layer tensors are
described once, stacked on a leading layer axis (``...layers.*...``).  The
same generator serves the plain reference (which consumes the stacked form
directly) and the program (through the family's adapter, which lays the
same values out as the program's parameter tree inside the same jitted
call).  Same seed, same device kind: same values on both sides.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """Any whole seed (the driver's pass 2**31) to a PRNG key."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2147483647), seed // 2147483647)


def _generate(spec: dict, key: jax.Array) -> dict:
    out = {}
    for i, name in enumerate(sorted(spec)):
        shape, mean, std = spec[name]
        if std == 0.0:
            out[name] = jnp.full(shape, mean, jnp.float32)
            continue
        k = jax.random.fold_in(key, i)
        out[name] = mean + std * jax.random.normal(k, shape, jnp.float32)
    return out


def make_reference_weights(spec: dict, seed: int) -> dict:
    """name -> float32 array, in the reference's (published, stacked) layout."""
    return jax.jit(lambda k: _generate(spec, k))(seed_key(seed))


def make_program_weights(spec: dict, seed: int, to_program_tree: Callable[[dict], Any]) -> Any:
    """The same values as the program's parameter tree (float32)."""
    return jax.jit(lambda k: to_program_tree(_generate(spec, k)))(seed_key(seed))
