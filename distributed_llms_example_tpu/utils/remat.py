"""Activation-checkpointing (remat) policies for transformer blocks.

``--remat`` trades compute for memory by recomputing block activations in
the backward pass.  The *policy* decides what still gets saved:

- ``full``: save nothing — maximum memory savings, recomputes the whole
  block.
- ``dots``: ``jax.checkpoint_policies.dots_with_no_batch_dims_saveable``
  — save matmul outputs (cheap to store, expensive to recompute on the
  MXU) and recompute only the elementwise/softmax glue (cheap to
  recompute, expensive to store).  The standard middle ground for
  7B-class models that fit activations-of-matmuls but not everything.

Numerics are identical across policies (remat never changes math, only
what is recomputed); ``tests/test_train_step.py`` asserts it.
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax

POLICIES: dict[str, Any] = {
    "full": None,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}

# keep the CLI choices (core/config.py, importable without jax) in sync
from distributed_llms_example_tpu.core.config import REMAT_POLICIES  # noqa: E402

assert set(REMAT_POLICIES) == set(POLICIES), (REMAT_POLICIES, tuple(POLICIES))


def remat_block(cls: Any, static_argnums: Sequence[int], policy: str = "full") -> Any:
    """``nn.remat`` wrapper honoring a named checkpoint policy."""
    try:
        chosen = POLICIES[policy]
    except KeyError:
        raise ValueError(
            f"remat_policy={policy!r}: must be one of {sorted(POLICIES)}"
        ) from None
    if chosen is None:
        return nn.remat(cls, static_argnums=tuple(static_argnums))
    return nn.remat(cls, static_argnums=tuple(static_argnums), policy=chosen)
