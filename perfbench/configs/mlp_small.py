"""Plain reference of the paper task's MLP as this repository runs it,
the benchmark's weights for it, and its operation counts.

Dense layers input_dim -> hidden_dims ... -> num_classes with a bias
each and ReLU between them, no activation on the logits; the loss is
the mean softmax cross-entropy over the batch. Written in plain
``jax.numpy``; it imports nothing of the program. Weights are made by
``init_params`` from the seed for the program's parameter tree
(``l0``, ``l1``, ... each with ``w`` and ``b``), in one jitted call.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _leaf_init(path, shape, key):
    leaf = getattr(path[-1], "key", str(path[-1]))
    normal = jax.random.normal(key, shape, jnp.float32)
    if leaf == "b":
        return 0.02 * normal
    return normal / math.sqrt(shape[0])


def init_params(shapes, key):
    """Random float32 weights for the parameter tree ``shapes``, one
    key per leaf folded from ``key``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [_leaf_init(path, tuple(s.shape), jax.random.fold_in(key, i))
              for i, (path, s) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def cast(tree, dtype):
    """Floating leaves to ``dtype`` (the controls); integers stay."""
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def logits(params, x):
    n = len(params)
    for i in range(n):
        layer = params[f"l{i}"]
        x = x @ layer["w"] + layer["b"]
        if i < n - 1:
            x = jnp.maximum(x, 0)
    return x


def loss(params, batch):
    """Mean softmax cross-entropy of ``batch`` (``x`` (b, d), ``y``
    (b,) int), taken in float32."""
    lg = logits(params, batch["x"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    pick = jnp.take_along_axis(lg, batch["y"][:, None], axis=-1)[:, 0]
    return jnp.mean(lse - pick)


def dims(cfg):
    return [cfg["input_dim"], *cfg["hidden_dims"], cfg["num_classes"]]


def num_params(cfg):
    d = dims(cfg)
    return sum(a * b + b for a, b in zip(d[:-1], d[1:]))


def train_flops(cfg, batch):
    """Operations of one client step (forward and backward, 2 per
    multiply-add, 3 passes) over a batch of ``batch`` examples."""
    d = dims(cfg)
    return 3 * 2 * batch * sum(a * b for a, b in zip(d[:-1], d[1:]))
