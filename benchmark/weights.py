"""Weights from the seed, on the device, in one jitted call.

The benchmark owns the weights: the system under test and the plain
reference are both handed these, so neither takes a number from the other.
Shapes and leaf names come from the parameter tree the entry built; values
are drawn per leaf from ``fold_in(key(seed), leaf index)``:

- a leaf named ``scale`` is ``1 + 0.05 n``; one named ``bias`` is ``0.02 n``
  (not the usual ones and zeros: a generic value shows a dropped term);
- any other leaf is ``std * n`` with the configuration's ``init.std``, or
  ``1 / sqrt(fan_in)`` where the configuration says ``"fan_in"``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def leaf_names(tree):
    """``path/joined/by/slashes`` of each leaf, in flattening order."""
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            for path, _ in paths]


def make(tree, seed: int, init: dict):
    """A tree like ``tree`` with every leaf drawn from ``seed``."""
    leaves, treedef = jax.tree.flatten(tree)
    names = leaf_names(tree)
    shapes = [tuple(a.shape) for a in leaves]

    def draw(key):
        out = []
        for i, (name, shape) in enumerate(zip(names, shapes)):
            n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            last = name.rsplit("/", 1)[-1]
            if last == "scale":
                out.append(1.0 + 0.05 * n)
            elif last == "bias":
                out.append(0.02 * n)
            elif init.get("std") == "fan_in":
                out.append(n / math.sqrt(max(1, math.prod(shape[:-1]))))
            else:
                out.append(float(init["std"]) * n)
        return out

    return jax.tree.unflatten(treedef, jax.jit(draw)(jax.random.key(seed)))
