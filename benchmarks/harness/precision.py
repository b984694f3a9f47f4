"""The matrix product the plain references go through, at a stated precision.

``fp32`` is the reference itself: float32 operands at ``highest`` (on a TPU
a float32 product otherwise runs in bfloat16 passes).  The other is the
control of "How correct is decided": the reference computed one step below
the precision the configuration states, to prove that the limits catch it.

* ``int8``: both operands of every product rounded to 255 levels of their
  own largest magnitude (symmetric, per tensor), the product of the rounded
  values accumulated exactly, the result rounded to bfloat16 as a bfloat16
  program would store it (so the control is nowhere finer than the stated
  precision).  Gradients pass straight through the operand rounding, as in
  int8 training recipes.  The control for a bfloat16 configuration.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

PRECISIONS = ("fp32", "int8")


def _round_int8(x: jnp.ndarray) -> jnp.ndarray:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def make_dot(precision: str) -> Callable[[str, jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """``dot(subscripts, a, b)``: an einsum of two float32 operands."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}: one of {PRECISIONS}")

    def dot(subscripts: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        if precision == "int8":
            out = jnp.einsum(subscripts, _round_int8(a), _round_int8(b), precision=jax.lax.Precision.HIGHEST)
            return out.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.einsum(subscripts, a, b, precision=jax.lax.Precision.HIGHEST)

    return dot
