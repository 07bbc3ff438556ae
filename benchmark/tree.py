"""Readings of a parameter-shaped tree that both sides of the check take
with the same code: per-leaf norms, and a sketch of each leaf on random
sign vectors drawn from a key, from which the norm of the difference of
two trees is estimated without either side keeping the other's tensors.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

SKETCH_K = 32  # sign vectors per leaf: the 32 bits of one uint32 per entry


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def leaf_diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)


def hash_bits(shape, salt):
    """A uint32 of 32 well-mixed bits for every entry of `shape`, from
    the entry's index and `salt` alone (the integer hash "lowbias32"):
    the same wherever it is computed, and cheap beside a counter-based
    random generator, which took 1.4 s for 136 M entries on a v5e."""
    n = 1
    for d in shape:
        n *= d
    x = jax.lax.iota(jnp.uint32, n).reshape(shape) + salt
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def sketch(tree, key):
    """Per leaf, its SKETCH_K sums over entries x * s_k, s_k = +-1 drawn
    from `key`. For two trees sketched with one key, the mean over k of
    (S_a - S_b)_k ** 2 estimates |a - b| ** 2 of that leaf (exact in
    expectation, within about an eighth of the norm at K = 32)."""
    leaves, treedef = jax.tree.flatten(tree)
    salts = jax.random.bits(key, (len(leaves),), jnp.uint32)
    out = []
    for i, x in enumerate(leaves):
        bits = hash_bits(x.shape, salts[i])
        x = x.astype(jnp.float32)
        out.append(jnp.stack(
            [jnp.sum(jnp.where((bits >> j) & 1, x, -x)) for j in range(SKETCH_K)]))
    return jax.tree.unflatten(treedef, out)


def first_gradient(grads, key):
    """What the check reads of a first gradient: per-leaf norms and
    sketch. One function for the program's side (on Adam's first moment)
    and the reference's, so one compiled program serves both."""
    return leaf_norms(grads), sketch(grads, key)


def flat_numbers(tree) -> Dict[str, np.ndarray]:
    """The tree's leaves as host numbers under their key paths: a float
    for a scalar leaf, an array otherwise."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf, dtype=np.float64)
        out[jax.tree_util.keystr(path)] = float(a) if a.ndim == 0 else a
    return out
