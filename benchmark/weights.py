"""The weights of a run, made by the benchmark from `--seed`: on the
device, in one jitted call, in float32 (the type the learner keeps its
parameters in; bfloat16 is its compute type). The learner under test and
the plain reference are both handed these; neither makes its own.

Kernels are normal with standard deviation 1/sqrt(fan_in), biases zero
(the forget gate's +1 is in the cell's equations, not in the bias), which
is the scale the program's own initialiser gives. The normals are
Box-Muller's of two hashed indices (`tree.hash_bits`), not threefry's: a
run makes the 136 M weights twice in set-up (the learner's, and again
inside the program that reads the parameters' change) and once for the
reference, and threefry took 1.4 s each time on a v5e.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.tree import hash_bits


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2147483629), seed // 2147483629)


def maker(shapes: dict):
    """key -> the parameter tree, as a pure function. `shapes`: the tree
    with a tuple for each leaf, as the configuration's reference module
    gives it (`param_shapes`)."""
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))

    def uniform(shape, salt):  # in (0, 1), 24 bits
        return ((hash_bits(shape, salt) >> 8).astype(jnp.float32) + 0.5) * (1.0 / (1 << 24))

    def make(key):
        salts = jax.random.bits(key, (len(leaves), 2), jnp.uint32)
        out = []
        for i, shape in enumerate(leaves):
            if len(shape) == 1:
                out.append(jnp.zeros(shape, jnp.float32))
            else:
                radius = jnp.sqrt(-2.0 * jnp.log(uniform(shape, salts[i, 0])))
                z = radius * jnp.cos((2.0 * math.pi) * uniform(shape, salts[i, 1]))
                out.append(z / math.sqrt(float(shape[0])))
        return jax.tree.unflatten(treedef, out)

    return make


def make_params(shapes: dict, seed: int, out_shardings=None):
    return jax.jit(maker(shapes), out_shardings=out_shardings)(seed_key(seed))
