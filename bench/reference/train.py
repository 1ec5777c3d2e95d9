"""Training steps by the reference: loss, first gradient, the change of the
parameters and both moments, to compare with what the program's step made.

The loss is the mean next-token cross entropy plus the z-loss (weight times
the mean squared log-partition), as the configuration states. The gradient
is clipped to a global norm and fed to AdamW with the traffic's schedule.
Parameters are kept in the configuration's dtype between steps and every
product is taken in float32 (or float8 for the control).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.numerics import Numerics


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to a tenth of the peak."""
    warm, total, peak = opt["warmup_steps"], opt["schedule_steps"], opt["lr"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.45 * (1.0 + math.cos(math.pi * prog)))


def _block_objective(model, m: dict, num: Numerics):
    def f(p32, tokens, labels):
        h = model.hidden(m, p32, tokens, num)
        logits = num.mm(h, model.unembed(p32).T)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.sum(lse - tgt) + m["z_loss_weight"] * jnp.sum(lse * lse)
    return f


def leaf_norms(tree) -> dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(k): float(n) for (k, _), n in zip(flat, norms)}


def run(model, m: dict, opt: dict, params, batches: list[dict], num: Numerics,
        rows_per_block: int, change_after: int) -> dict:
    """Train ``len(batches)`` steps from ``params`` (which it consumes).
    Returns per-step losses, the first step's clipped gradient leaf norms,
    the leaf norms of the parameter change after ``change_after`` steps, and
    after the last step those of the change and of both moments."""
    obj = jax.jit(jax.value_and_grad(_block_objective(model, m, num)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    init = jax.tree.map(lambda x: x, params)
    delta = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))

    @jax.jit
    def adam(params, mu, nu, grads, n_tok, t, lr):
        grads = jax.tree.map(lambda g: g / n_tok, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        b1, b2 = opt["beta1"], opt["beta2"]
        mu = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, m_, v):
            p32 = p.astype(jnp.float32)
            u = (m_ / bc1) / (jnp.sqrt(v / bc2) + opt["eps"]) \
                + opt["weight_decay"] * p32
            return (p32 - lr * u).astype(p.dtype)

        return jax.tree.map(upd, params, mu, nu), mu, nu, grads

    mu = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    nu = mu
    losses, first_grad, change = [], None, None
    for step, batch in enumerate(batches):
        rows = batch["tokens"].shape[0]
        n_tok = rows * batch["tokens"].shape[1]
        total, grads = 0.0, None
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        for r in range(0, rows, rows_per_block):
            v, g = obj(p32, batch["tokens"][r:r + rows_per_block],
                       batch["labels"][r:r + rows_per_block])
            total += float(v)
            grads = g if grads is None else add(grads, g)
        del p32
        losses.append(total / n_tok)
        params, mu, nu, clipped = adam(params, mu, nu, grads,
                                       jnp.float32(n_tok),
                                       jnp.float32(step + 1),
                                       jnp.float32(lr_at(opt, step)))
        if step == 0:
            first_grad = leaf_norms(clipped)
        if step + 1 == change_after:
            change = leaf_norms(delta(params, init))
        del grads, clipped
    end = {"change": leaf_norms(delta(params, init)), "mu": leaf_norms(mu),
           "nu": leaf_norms(nu)}
    return {"losses": losses, "first_grad": first_grad, "change": change,
            "end": end}


def worst_leaf_gap(prog: dict[str, float], ref: dict[str, float],
                   counted: list[str]) -> tuple[float, str]:
    """Largest |prog - ref| over the counted leaves, each measured against
    the larger of its own reference norm and the median leaf's."""
    med = float(np.median([ref[k] for k in counted]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in counted}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def counted_leaves(ref_grad: dict[str, float]) -> list[str]:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's."""
    med = float(np.median(list(ref_grad.values())))
    return sorted(k for k, v in ref_grad.items() if v >= 1e-3 * med)
