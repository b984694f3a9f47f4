"""The plain training reference: three optimizer steps in float32.

Follows what the published recipe says a step is — mean token cross-entropy
over the optimizer batch, global-norm clipping, AdamW with decoupled decay
on matrices and embeddings, linear warm-up-then-decay of the rate — on the
family's plain forward pass, in blocks of rows so that it fits beside
nothing else.  It imports nothing of the program and takes nothing the
program made: weights come from the seed, batches from the caller (checked
against the benchmark's own tokenization before they get here).
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import precision as precision_mod
from benchmarks.harness import weights as weights_mod
from benchmarks.harness.program import reference_norms, reference_samples
from benchmarks.harness.text import LABEL_PAD


def learning_rate(opt: dict, count: int) -> float:
    """Linear warm-up to ``learning_rate`` then linear decay to 0 at
    ``total_steps`` (``get_scheduler('linear')``), at 0-based step ``count``."""
    lr, warm, total = opt["learning_rate"], int(opt["warmup_steps"]), int(opt["total_steps"])
    if count < warm:
        return lr * count / max(1, warm)
    return lr * max(0.0, 1.0 - (count - warm) / max(1, total - warm))


def decays(name: str, ndim: int) -> bool:
    """Decoupled weight decay applies to matrices and embeddings only."""
    return ndim - (1 if ".*" in name else 0) >= 2


def follow(ref: Any, cfg: dict, seed: int, batches: list[dict], opt: dict, *,
           precision: str = "fp32", rows_per_block: int = 2) -> dict:
    """Run ``len(batches)`` steps; return each step's loss, the per-leaf norm
    of the first (clipped) gradient, and of the parameters' change after the
    last step.  ``batches``: dicts of numpy ``input_ids``, ``attention_mask``,
    ``labels``."""
    t0 = time.perf_counter()
    dot = precision_mod.make_dot(precision)
    spec = ref.param_spec(cfg)
    start, shifted_pad = ref.decoder_start(cfg)
    params = weights_mod.make_reference_weights(spec, seed)
    names = sorted(params)

    def loss_sum(p, ids, mask, labels):
        dec_in = jnp.roll(labels, 1, axis=-1).at[:, 0].set(start)
        dec_in = jnp.where(dec_in == LABEL_PAD, shifted_pad, dec_in)
        logits = ref.forward(p, cfg, ids, mask, dec_in, dot)
        valid = labels != LABEL_PAD
        logz = jax.nn.logsumexp(logits, axis=-1)
        true = jnp.take_along_axis(logits, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(valid, logz - true, 0.0))

    @jax.jit
    def zeros_like_tree(p):
        return jax.tree.map(jnp.zeros_like, p)

    def accumulate(p, g_acc, l_acc, ids, mask, labels):
        l, g = jax.value_and_grad(loss_sum)(p, ids, mask, labels)
        return jax.tree.map(jnp.add, g_acc, g), l_acc + l

    accumulate = jax.jit(accumulate, donate_argnums=(1, 2))

    def clip(g_sum, tokens):
        g = jax.tree.map(lambda x: x / tokens, g_sum)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        factor = opt["max_grad_norm"] / jnp.maximum(norm, opt["max_grad_norm"]) if opt["max_grad_norm"] > 0 else 1.0
        return jax.tree.map(lambda x: x * factor, g), norm

    clip = jax.jit(clip, donate_argnums=(0,))

    def adamw(p, mu, nu, g, lr, t):
        b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
        new_p, new_mu, new_nu = {}, {}, {}
        for n in names:
            m = b1 * mu[n] + (1 - b1) * g[n]
            v = b2 * nu[n] + (1 - b2) * jnp.square(g[n])
            u = (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + eps)
            if decays(n, p[n].ndim):
                u = u + wd * p[n]
            new_p[n], new_mu[n], new_nu[n] = p[n] - lr * u, m, v
        return new_p, new_mu, new_nu

    adamw = jax.jit(adamw, donate_argnums=(0, 1, 2, 3))

    mu, nu = zeros_like_tree(params), zeros_like_tree(params)
    losses, first_grad, global_norms = [], None, []
    for step, batch in enumerate(batches):
        g_acc, l_acc = zeros_like_tree(params), jnp.zeros((), jnp.float32)
        n_rows = batch["input_ids"].shape[0]
        for r in range(0, n_rows, rows_per_block):
            sl = slice(r, r + rows_per_block)
            g_acc, l_acc = accumulate(
                params, g_acc, l_acc, jnp.asarray(batch["input_ids"][sl]),
                jnp.asarray(batch["attention_mask"][sl]), jnp.asarray(batch["labels"][sl]),
            )
        tokens = float(np.sum(batch["labels"] != LABEL_PAD))
        losses.append(float(l_acc) / tokens)
        g, norm = clip(g_acc, tokens)
        global_norms.append(float(norm))
        if step == 0:
            first_grad, first_grad_samples = reference_norms(g), reference_samples(g)
        params, mu, nu = adamw(params, mu, nu, g, learning_rate(opt, step), float(step + 1))
    del mu, nu, g
    initial = weights_mod.make_reference_weights(spec, seed)
    delta = reference_norms(jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(params, initial))
    jax.block_until_ready(params)
    return {
        "losses": losses, "first_grad_norms": first_grad, "first_grad_samples": first_grad_samples, "delta_norms": delta,
        "grad_global_norms": global_norms, "seconds": time.perf_counter() - t0,
    }
