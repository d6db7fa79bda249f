"""The comparisons that decide `correct`, each made outside the timed
window.

(a) `reference_check`: on a seeded sample at the published widths, the
    program's loss and the gradient of every leaf against the plain
    float32 reference of the family.
(b) `plain_losses` / `losses_agree`: the first losses of the measured path
    against a plain `jax.value_and_grad` + optax step that has no
    gradient exchange in it.
(c) `losses_sound`: finite in every step, lower at the end than at the
    start.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax
from jax import lax

from benchmark.harness import seeded

# (a) The tolerances are the configuration's own (`reference_check` in its
# file, beside their reason): how far bfloat16 compute moves a gradient
# depends on the architecture and its widths.

# (b) One worker's PS round returns the pushed bits, so the PS path
# differs from the plain step only in how XLA fuses the optimizer when it
# is a program of its own: float32 rounding of a loss near 10.  Across
# four chips the gradient is a sum in another order and the loss a mean
# of four shard means; PR 23's smoke run showed 1.8e-5.
PLAIN_LOSS_REL_TOL = {"rounding": 2e-6, "reduction_order": 1e-4}


def _rel_diff_and_norm_ratio(a, b):
    a = a.astype(jnp.float32).ravel()
    b = b.astype(jnp.float32).ravel()
    ref = jnp.linalg.norm(b) + 1e-30
    return jnp.stack([jnp.linalg.norm(a - b) / ref,
                      jnp.linalg.norm(a) / ref])


def reference_value_and_grad(reference_loss, params, batch):
    """The reference's loss and gradient on `batch`, one sample at a
    time: both families' losses are means over samples of equal weight,
    so the mean of the per-sample results is the batch's.  Without
    rematerialisation the reference keeps every activation in float32
    (for gpt2-medium four S x S tensors a layer, 6.5 GB a sequence), and
    two samples at once do not fit beside the program's own gradients."""
    @jax.jit
    def one(params, batch, i):
        sample = jax.tree.map(
            lambda x: lax.dynamic_slice_in_dim(x, i, 1, axis=0), batch)
        return jax.value_and_grad(reference_loss)(params, sample)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    n = jax.tree.leaves(batch)[0].shape[0]
    total = one(params, batch, 0)
    for i in range(1, n):
        total = add(total, one(params, batch, i))
    return jax.jit(lambda t: jax.tree.map(lambda x: x / n, t))(total)


def gradient_agreement(loss, reference_loss, params, batch) -> dict:
    """Loss and per-leaf gradient of `loss` against `reference_loss` on
    the same params and batch: the relative loss difference and, over the
    leaves, the worst norm of the gradient difference over the norm of
    the reference's gradient (direction and size together) and the norm
    ratio furthest from 1 (size alone: a gradient scaled by a constant
    shows here however loose the first has to be)."""
    got_loss, got = jax.jit(jax.value_and_grad(loss))(params, batch)
    ref_loss, ref = reference_value_and_grad(reference_loss, params, batch)
    both = jax.jit(lambda g, r: jax.tree.map(_rel_diff_and_norm_ratio, g, r))(
        got, ref)
    leaves = {jax.tree_util.keystr(path): [float(x) for x in v] for path, v
              in jax.tree_util.tree_flatten_with_path(both)[0]}
    worst = max(leaves, key=lambda k: leaves[k][0])
    skewed = max(leaves, key=lambda k: abs(leaves[k][1] - 1.0))
    got_loss, ref_loss = float(got_loss), float(ref_loss)
    return {"loss": got_loss, "reference_loss": ref_loss,
            "loss_rel_diff": abs(got_loss - ref_loss) / abs(ref_loss),
            "worst_leaf": worst, "worst_grad_rel_diff": leaves[worst][0],
            "most_rescaled_leaf": skewed,
            "worst_grad_norm_ratio": leaves[skewed][1]}


def agreement_ok(agreement: dict, tolerances: dict) -> bool:
    return (math.isfinite(agreement["loss"])
            and agreement["loss_rel_diff"] <= tolerances["loss_rel_tol"]
            and agreement["worst_grad_rel_diff"] <= tolerances["grad_rel_tol"]
            and abs(agreement["worst_grad_norm_ratio"] - 1.0)
            <= tolerances["grad_norm_tol"])


def reference_check(family, seed: int) -> dict:
    """(a) at the family's own widths, on as many samples made from
    `seed` as its configuration says.  Frees what it made."""
    out = gradient_agreement(
        family.loss, family.reference_loss, seeded.params(family, seed),
        seeded.batch(family, seed, family.reference_check["samples"]))
    out["ok"] = agreement_ok(out, family.reference_check)
    return out


def plain_losses(family, params, shards, steps: int) -> list:
    """(b) the losses of `steps` plain training steps on one device: the
    gradient of each shard in turn, their mean, one optax update.  No
    byteps_tpu call is in it.  `params` is consumed."""
    opt = family.optimizer()
    grad = jax.jit(jax.value_and_grad(family.loss))

    @jax.jit
    def mean(trees):
        return jax.tree.map(lambda *xs: sum(xs) / len(xs), *trees)

    def apply(params, opt_state, grads):
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    apply = jax.jit(apply, donate_argnums=(0, 1))
    opt_state = jax.jit(opt.init)(params)
    losses = []
    for _ in range(steps):
        outs = [grad(params, shard) for shard in shards]
        loss, grads = (outs[0] if len(outs) == 1 else mean(outs))
        params, opt_state = apply(params, opt_state, grads)
        losses.append(float(loss))
    return losses


def losses_agree(got: list, plain: list, kind: str) -> dict:
    tol = PLAIN_LOSS_REL_TOL[kind]
    worst = max(abs(a - b) / abs(b) for a, b in zip(got, plain))
    return {"measured_path": got, "plain": plain, "worst_rel_diff": worst,
            "tolerance": tol, "ok": worst <= tol}


def losses_sound(losses: list) -> bool:
    """(c)"""
    return (len(losses) >= 2 and all(math.isfinite(x) for x in losses)
            and losses[-1] < losses[0])
