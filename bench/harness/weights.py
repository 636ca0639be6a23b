"""Random weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program's own
initialiser is only asked for the shapes of its parameter tree
(``jax.eval_shape``), and every leaf is drawn here, in the type it is
served in.  The reference reads the same arrays by leaf name.

Scales follow the usual pre-norm recipe: a projection from width ``n``
has standard deviation ``1 / sqrt(n)``, the two projections back into the
residual stream are further scaled by ``1 / sqrt(2 * layers)``, and the
output head takes 0.02.  Embeddings take 1, not the usual 0.02: at 0.02
the token read is lost under the layers' sum, and a deep random model puts
its first choice on the same few tokens whatever the context (rank
collapse), so its greedy output would not depend on the attention or the
cache the check is there to test.  Norm gains are ``0.1 * N(0, 1)``
rather than zero, so that the reference has to apply them as the program
does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole ``seed`` up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def leaf_std(name: str, shape, sizes: dict) -> float:
    L = sizes["n_layers"]
    out = 1.0 / math.sqrt(2 * L)
    last = name.rsplit("/", 1)[-1]
    if last == "embed":
        return 1.0
    if last == "head":
        return 0.02
    if last.startswith("ln"):
        return 0.1
    if last in ("wq", "wk", "wv", "w_in"):
        return 1.0 / math.sqrt(shape[1])                    # [L, D, ...]
    if last == "wo":
        return out / math.sqrt(shape[1] * shape[2])         # [L, H, hd, D]
    if last == "w_out":
        return out / math.sqrt(shape[1])                    # [L, F, D]
    raise ValueError(f"no initialisation rule for parameter {name!r}")


def make_params(shapes, sizes: dict, seed: int):
    """Arrays shaped and typed like ``shapes`` (a tree of
    ``ShapeDtypeStruct``), drawn from ``seed`` on the default device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [(leaf_std(leaf_name(p), s.shape, sizes), s.shape, s.dtype)
             for p, s in flat]

    def draw(key):
        keys = jax.random.split(key, len(rules))
        leaves = [(jax.random.normal(k, shape, dtype) * jnp.asarray(std, dtype))
                  for k, (std, shape, dtype) in zip(keys, rules)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(seed_key(seed))
