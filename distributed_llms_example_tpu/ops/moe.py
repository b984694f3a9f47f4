"""Mixture-of-experts MLP with expert parallelism (Mixtral-style).

The reference has no MoE (SURVEY.md §2: expert parallel "out of scope");
this module goes past parity so the LLaMA family extends to
Mixtral-class sparse models.  TPU-first design choices:

- **Dense dispatch in fixed-size groups** (GShard/Switch formulation):
  tokens are routed within groups of ``group_size``, and routing is
  expressed as two einsums against a (group, tokens, experts, capacity)
  one-hot dispatch tensor — the whole layer is static-shaped matmuls the
  MXU executes and XLA can partition; no ragged gather/scatter, no
  data-dependent shapes, and activation memory linear in sequence length
  (per-group dispatch is O(group_size²·K/E), ~167 MB fp32 at the 4096
  default with E=8/K=2).  Tokens over an expert's per-group capacity are
  dropped (their output is 0; the block's residual connection carries
  them through), the standard capacity-factor trade.
- **Expert parallelism via GSPMD**: the stacked expert weights
  (E, d_in, d_out) shard their leading dim over the ``tensor`` mesh axis
  (see ``parallel/sharding.py`` EXPERT rules), and the expert-major
  activations (G, E, capacity, d) are constrained to the same axis — the
  partitioner then lowers the dispatch/combine einsums to the expert
  all-to-all over ICI, with zero hand-written collectives.
- **Router in fp32** — softmax over experts is precision-sensitive, the
  same policy as attention softmax (core/precision.py).
- **No dropped tokens: routing by sorted assignment** (``no_drop``: every
  cached path, and every path of a config with ``capacity_factor <= 0``).
  The (tokens x top_k) assignments are sorted by expert, the token rows
  gathered in that order, and the three expert products run as grouped
  products over the experts (``grouped_dot`` below: reads only the experts
  that received rows); the results are unsorted and summed under their gate
  weights.  Memory is
  linear in tokens x top_k: there is no (tokens, experts, capacity)
  tensor on this path, whatever the expert count.
- **The scorer comes from the model's config**: ``softmax`` over the
  experts, top-k renormalised (Mixtral), or ``sigmoid`` with a per-expert
  selection bias that chooses the experts and never enters their weights
  (LFM2 / DeepSeek-V3 ``noaux_tc``: the bias is a buffer the load
  balancer moves, not a trained weight — ``stop_gradient`` here).
- The load-balancing auxiliary loss (E · Σ_e fraction_e · prob_e with
  all top-k assignments in the fraction — HF Mixtral's
  ``load_balancing_loss_func``, = top_k at uniform routing) is ``sow``-n
  into the ``losses`` collection; the train step adds it when
  ``config.moe_aux_weight > 0`` and generation (which never mutates
  ``losses``) silently discards it.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_llms_example_tpu.parallel.activation import constrain, current_mesh


def gmm_tiling(m: int, k: int, n: int, itemsize: int) -> tuple[int, int, int] | None:
    """(rows, contraction, columns) tile of the Pallas grouped product for an
    (m, k) x (experts, k, n) call, from its shapes; None where it does not
    tile (a toy width) and the plain ``ragged_dot`` runs instead.

    What decides it (v5e, PR 28, 32 experts of 2048 x 1792, bfloat16; PERF.md):
    a decode round has 512 rows, ~16 an expert, so every expert's weights are
    read for a handful of rows and the call is bound by that read: a 128-row
    tile (XLA's ``ragged_dot`` takes 512 and spends 0.85-1.12 ms on rows that
    are not there; 128 takes 0.37, 78 % of the 0.29 ms the bytes need), the
    whole contraction and half the columns a step, so that an expert's slab
    is two long DMAs.  A prefill wave has 16,384 rows, ~512 an expert, and is
    bound by the MXU: 256-row tiles waste less at the group boundaries than
    512 (1.07 ms against 1.28; ``ragged_dot`` 2.27; 0.61 at the peak)."""
    if k % 128 or n % 128 or k > 4096:
        return None
    tm = 128 if m < 4096 else 256
    slab = 4 * 2**20  # bytes of one (tk, tn) weight tile; double-buffered beside the rows and the accumulator
    tn = max((t for t in range(128, n + 1, 128) if n % t == 0 and k * t * itemsize <= slab), default=None)
    return None if tn is None else (tm, k, tn)


def grouped_dot(rows: jnp.ndarray, weights: jnp.ndarray, load: jnp.ndarray) -> jnp.ndarray:
    """``rows`` (m, k), sorted by expert, times each row's expert's matrix of
    ``weights`` (experts, k, n); ``load`` (experts,) int32 counts the rows of
    each expert.  On one TPU chip at widths that tile: the Pallas grouped
    matmul that ships with jax (``megablox.gmm``, differentiable) under
    ``gmm_tiling``.  Otherwise ``jax.lax.ragged_dot``, which XLA can partition
    over a mesh and every backend runs (on the CPU: the tests)."""
    m, k = rows.shape
    tiling = gmm_tiling(m, k, weights.shape[-1], rows.dtype.itemsize)
    mesh = current_mesh()
    one_chip = jax.device_count() == 1 or mesh is None or math.prod(mesh.devices.shape) == 1
    if tiling is None or not one_chip or jax.default_backend() != "tpu" or rows.dtype != weights.dtype:
        return jax.lax.ragged_dot(rows, weights, load)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    pad = -m % tiling[0]  # rows past the last group belong to no expert and are cut off again
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, weights, load, rows.dtype, tiling)
    return out[:m] if pad else out


def _expert_spec():
    """(groups, experts, capacity, d_model) — experts over ``expert``."""
    from jax.sharding import PartitionSpec as P

    return P(None, "expert")


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU experts; drop-in for a dense gated MLP.

    Shapes: E experts, each a SwiGLU of (d_model → ff → d_model) with
    stacked weights (E, ...).  ``capacity_factor`` scales each expert's
    token budget: capacity = ceil(top_k · N / E · factor).
    """

    num_experts: int
    intermediate_size: int
    top_k: int = 2
    capacity_factor: float = 1.25
    # routing group size (GShard): tokens are routed within fixed-size
    # groups, so the (group, E, capacity) dispatch tensors stay
    # O(group_size²) per group and total activation memory is LINEAR in
    # sequence length — without grouping the dense dispatch is quadratic
    # and cannot fit 32k-context mixtral-8x7b on a 16 GB chip
    group_size: int = 4096
    dtype: jnp.dtype = jnp.float32
    # how the router's logits become gate weights: "softmax" over the experts
    # (Mixtral) or "sigmoid" per expert (LFM2); the top-k are chosen on
    # score + ``expert_bias`` where the model has one, and weighted by the score
    scorer: str = "softmax"
    use_expert_bias: bool = False
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # sow the load-balance loss (a model trained without one has none to add)
    aux_loss: bool = True

    @nn.compact
    def __call__(self, x: jnp.ndarray, no_drop: bool = False) -> jnp.ndarray:
        """``no_drop=True`` (cached decode/prefill) drops no token: the
        sorted-assignment path (module docstring).  ``capacity_factor <= 0``
        makes the layer no-drop on EVERY path, including teacher-forced
        scoring and fine-tuning — HF Mixtral routes densely with no
        capacity limit, so converted checkpoints load with that setting
        (registry) to reproduce HF logits exactly everywhere."""
        if self.scorer not in ("softmax", "sigmoid"):
            raise ValueError(f"scorer={self.scorer!r}: must be 'softmax' or 'sigmoid'")
        b, s, d = x.shape
        E, K = self.num_experts, self.top_k
        n = b * s
        router = nn.Dense(E, use_bias=False, dtype=jnp.float32, name="router")
        bias = None
        if self.use_expert_bias:
            bias = jax.lax.stop_gradient(
                self.param("expert_bias", nn.initializers.zeros, (E,), jnp.float32)
            )
        w_gate = self.param(
            "gate_proj", nn.initializers.lecun_normal(), (E, d, self.intermediate_size)
        ).astype(self.dtype)
        w_up = self.param(
            "up_proj", nn.initializers.lecun_normal(), (E, d, self.intermediate_size)
        ).astype(self.dtype)
        w_down = self.param(
            "down_proj", nn.initializers.lecun_normal(), (E, self.intermediate_size, d)
        ).astype(self.dtype)

        def route(tokens):
            """(scores over all experts, top-k gate weights, top-k expert ids), fp32."""
            with jax.named_scope("moe_route"):
                logits = router(tokens.astype(jnp.float32))
                scores = (
                    jax.nn.softmax(logits, axis=-1) if self.scorer == "softmax"
                    else jax.nn.sigmoid(logits)
                )
                _, idx = jax.lax.top_k(scores if bias is None else scores + bias, K)
                gates = jnp.take_along_axis(scores, idx, axis=-1)
                if self.norm_topk_prob and K > 1:
                    # LFM2's modeling code guards the sum of sigmoids with 1e-6;
                    # Mixtral's softmax sum has no guard
                    gates = gates / (
                        jnp.sum(gates, axis=-1, keepdims=True)
                        + (1e-6 if self.scorer == "sigmoid" else 0.0)
                    )
                return scores, gates * self.routed_scaling_factor, idx

        def sow_aux(frac, mean_prob):
            # E · Σ_e fraction_e · mean-prob_e, the fraction counting ALL top-k
            # assignments (pre-capacity): HF Mixtral's load_balancing_loss_func
            # (= top_k at uniform routing; 1.0 for top-1, the Switch special
            # case), so a converted checkpoint's router_aux_loss_coef compares
            if self.aux_loss:
                self.sow(
                    "losses", "moe_aux", E * jnp.sum(frac * mean_prob),
                    reduce_fn=lambda a, b: a + b,
                    init_fn=lambda: jnp.zeros((), jnp.float32),
                )

        if no_drop or self.capacity_factor <= 0:
            tokens = x.reshape(n, d)
            scores, gates, idx = route(tokens)
            with jax.named_scope("moe_route"):
                # sort the n·K assignments by expert: row r of the sorted batch
                # is token order[r] // K under its (order[r] % K)-th choice
                flat = idx.reshape(n * K)
                order = jnp.argsort(flat, stable=True)
                load = jnp.bincount(flat, length=E).astype(jnp.int32)
                rows = jnp.take(tokens, order // K, axis=0)
            # this call's assignments per expert, for whoever collects the
            # collection (the serving engine's round counters); else a no-op
            self.sow("moe_stats", "load", load)
            sow_aux(load.astype(jnp.float32) / n, jnp.mean(scores, axis=0))
            with jax.named_scope("moe_experts"):
                h = nn.silu(grouped_dot(rows, w_gate, load))
                h = h * grouped_dot(rows, w_up, load)
                out = grouped_dot(h, w_down, load)
            with jax.named_scope("moe_route"):
                # back to (token, choice) order, then the gate-weighted sum
                inverse = jnp.zeros((n * K,), jnp.int32).at[order].set(
                    jnp.arange(n * K, dtype=jnp.int32)
                )
                out = jnp.take(out, inverse, axis=0).reshape(n, K, d)
                out = jnp.sum(out * gates[..., None].astype(out.dtype), axis=1)
            return out.reshape(b, s, d)

        g = min(self.group_size, n)
        G = -(-n // g)  # ceil
        n_pad = G * g - n
        tokens = x.reshape(n, d)
        if n_pad:
            tokens = jnp.pad(tokens, ((0, n_pad), (0, 0)))
        tokens = tokens.reshape(G, g, d)
        # pad tokens are excluded from routing (they claim no capacity)
        valid = (jnp.arange(G * g) < n).astype(jnp.float32).reshape(G, g)
        capacity = max(1, math.ceil(K * g / E * self.capacity_factor))
        probs, gate_vals, expert_idx = route(tokens)  # (G, g, E), (G, g, K) x 2

        # position-in-expert via in-group cumsum, k-th choices queue behind
        # (k-1)-th; tokens past an expert's capacity are dropped
        dispatch = jnp.zeros((G, g, E, capacity), jnp.float32)
        combine = jnp.zeros((G, g, E, capacity), jnp.float32)
        counts = jnp.zeros((G, E), jnp.float32)
        for k in range(K):
            mask_k = jax.nn.one_hot(expert_idx[..., k], E, dtype=jnp.float32)
            mask_k = mask_k * valid[..., None]  # (G, g, E)
            pos_k = jnp.cumsum(mask_k, axis=1) - mask_k + counts[:, None, :]
            counts = counts + jnp.sum(mask_k, axis=1)
            mask_k = mask_k * (pos_k < capacity)
            slot = jax.nn.one_hot(
                jnp.sum(pos_k * mask_k, axis=-1).astype(jnp.int32), capacity, dtype=jnp.float32
            )  # (G, g, cap)
            disp_k = mask_k[..., None] * slot[..., None, :]  # (G, g, E, cap)
            dispatch = dispatch + disp_k
            combine = combine + gate_vals[..., k, None, None] * disp_k

        # over REAL tokens; ``counts`` already holds Σ_k Σ_tokens of the
        # PRE-capacity (valid-masked) assignment one-hots of the loop above
        n_real = jnp.maximum(jnp.sum(valid), 1.0)
        sow_aux(
            jnp.sum(counts, axis=0) / n_real,  # sums to top_k
            jnp.sum(probs * valid[..., None], axis=(0, 1)) / n_real,
        )

        # dispatch → per-expert per-group batches, batched SwiGLU on the
        # MXU (experts broadcast over groups), combine
        expert_in = jnp.einsum("Gnec,Gnd->Gecd", dispatch.astype(self.dtype), tokens)
        expert_in = constrain(expert_in, _expert_spec())
        h = nn.silu(jnp.einsum("Gecd,edf->Gecf", expert_in, w_gate))
        h = h * jnp.einsum("Gecd,edf->Gecf", expert_in, w_up)
        expert_out = jnp.einsum("Gecf,efd->Gecd", h, w_down)
        expert_out = constrain(expert_out, _expert_spec())
        out = jnp.einsum("Gnec,Gecd->Gnd", combine.astype(self.dtype), expert_out)
        out = out.reshape(G * g, d)
        if n_pad:
            out = out[:n]
        return out.reshape(b, s, d)
