"""Random weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program's own
initialiser is only asked for the shapes of its parameter tree
(``jax.eval_shape``), and every leaf is drawn here, in the type it is
served in.  The reference reads the same arrays by leaf name.

Each leaf is ``N(0, 1)`` times the standard deviation that the family's
``leaf_std(name, shape, config)`` gives it (``bench/references/<name>.py``);
the keys are split from the seed in the tree's flattened order.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole ``seed`` up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def make_params(shapes, config: dict, seed: int, leaf_std: Callable):
    """Arrays shaped and typed like ``shapes`` (a tree of
    ``ShapeDtypeStruct``), drawn from ``seed`` on the default device by
    the rule ``leaf_std``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [(leaf_std(leaf_name(p), s.shape, config), s.shape, s.dtype)
             for p, s in flat]

    def draw(key):
        keys = jax.random.split(key, len(rules))
        leaves = [(jax.random.normal(k, shape, dtype) * jnp.asarray(std, dtype))
                  for k, (std, shape, dtype) in zip(keys, rules)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(seed_key(seed))
