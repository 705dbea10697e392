"""The comparison that decides ``correct``: the program's numbers against
the plain reference's, leaf by leaf, computed where the arrays are (a
gradient tree of a real configuration is gigabytes; only the errors come
back to the host).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


@jax.jit
def relative_l2(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.linalg.norm((got - want).ravel()) / jnp.maximum(
        jnp.linalg.norm(want.ravel()), 1e-30)


def check(name: str, error: float, tolerance: float) -> dict:
    error = float(error)
    return {"name": name, "error": error, "tolerance": tolerance,
            "ok": bool(math.isfinite(error) and error <= tolerance)}


def check_tree(name: str, got, want, tolerance: float) -> dict:
    """Worst relative L2 error over the leaves of two like trees.  A leaf
    is judged alone, so a fault in one small tensor cannot hide behind the
    norm of a large one."""
    got_leaves, treedef = jax.tree.flatten(got)
    want_leaves = treedef.flatten_up_to(want)
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(got)[0]]
    errors = [float(e) for e in jax.device_get(
        [relative_l2(g, w) for g, w in zip(got_leaves, want_leaves)])]
    worst = max(range(len(errors)),
                key=lambda i: errors[i] if math.isfinite(errors[i])
                else math.inf)
    out = check(name, errors[worst], tolerance)
    out["worst_leaf"] = paths[worst]
    out["median_leaf_error"] = sorted(errors)[len(errors) // 2]
    return out


def check_global(name: str, got, want, tolerance: float) -> dict:
    """Relative L2 error of two like trees taken as one long vector: for
    gradients whose single leaves are too noisy to judge alone."""
    got_leaves, treedef = jax.tree.flatten(got)
    want_leaves = treedef.flatten_up_to(want)
    diff, norm = jax.device_get(_sums_of_squares(got_leaves, want_leaves))
    return check(name, math.sqrt(float(diff)) / max(math.sqrt(float(norm)),
                                                     1e-30), tolerance)


@jax.jit
def _sums_of_squares(got, want):
    f32 = jnp.float32
    return (sum(jnp.sum(jnp.square(g.astype(f32) - w.astype(f32)))
                for g, w in zip(got, want)),
            sum(jnp.sum(jnp.square(w.astype(f32))) for w in want))


def mean_over(fn, argument_sets: list[tuple]):
    """The mean of ``fn(*args)`` (any tree of arrays) over the argument
    sets, one call at a time: the reference holds one backward at once."""
    add = jax.jit(lambda acc, new: jax.tree.map(jnp.add, acc, new),
                  donate_argnums=(0,))
    total = None
    for args in argument_sets:
        out = fn(*args)
        total = out if total is None else add(total, out)
    n = len(argument_sets)
    if n == 1:
        return total
    return jax.jit(lambda t: jax.tree.map(lambda x: x / n, t),
                   donate_argnums=(0,))(total)
