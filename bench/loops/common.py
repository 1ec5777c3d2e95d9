"""Helpers the loops share: the program's configuration against the
benchmark's, the reference of a configuration, and the weights."""
from __future__ import annotations

import functools
import importlib

from bench import harness as h


def check_program_config(program_cfg, model: dict) -> None:
    """The program must run the model the configuration file states."""
    wrong = {k: (getattr(program_cfg, k, None), v) for k, v in model.items()
             if getattr(program_cfg, k, None) != v}
    if wrong:
        raise h.BenchError(f"the program's config departs from the "
                           f"configuration file (program, file): {wrong}")


def reference(cell: h.Cell):
    return importlib.import_module(f"bench.reference.{cell.config['reference']}")


def make_weights(cell: h.Cell, seed: int):
    """The weights from the seed, on the device, in one jitted call."""
    import jax
    ref = reference(cell)
    return jax.jit(functools.partial(ref.init_params, cell.model))(
        h.seed_key(seed, 0))


def adopt_weights(new, old):
    """Place ``new`` where ``old`` lives, after checking that it has the
    program's tree, shapes and dtypes."""
    import jax
    if jax.tree.structure(new) != jax.tree.structure(old) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))):
        raise h.BenchError("the reference's weights do not fit the "
                           "program's parameter tree")
    return jax.tree.map(lambda a, b: jax.device_put(a, b.sharding), new, old)


def reference_gaps(ctx: h.Ctx, prompts, served, controls=()) -> dict:
    """The served tokens' widest logit gap under the reference (and, for
    each control, the gap of the tokens the control puts first)."""
    from bench.reference import serve
    return serve.served_gaps(reference(ctx.cell), ctx.cell.model,
                             make_weights(ctx.cell, ctx.seed), prompts,
                             served, controls)
