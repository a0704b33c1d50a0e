"""Model weights drawn from the run's seed, on the device, in one jitted
call.  The benchmark makes them (not the program's own initializer), so the
plain reference can draw the very same weights without taking anything the
program has made.  The tree's layout (leaf paths and shapes) is the
program's parameter interface and is checked against it by the caller."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BIAS_SCALE = 0.05


def key_of(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one past 32 bits, through
    numpy's SeedSequence."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def time_ladder(dim: int) -> np.ndarray:
    """TGAT's frequency ladder w_k = 10^(-9k/(dim-1))."""
    return 1.0 / np.power(10.0, 9.0 * np.arange(dim) / max(dim - 1, 1))


def _draw(key, path: tuple[str, ...], shape):
    if path[-2:] == ("time", "w"):
        return jnp.asarray(time_ladder(shape[0]), jnp.float32)
    if path[-2:] == ("time", "b"):
        return jnp.zeros(shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.float32)
    if path[-1] == "w" and len(shape) == 2:
        return z / np.sqrt(shape[0])
    return z * BIAS_SCALE


def make_params(seed: int, layout: dict) -> dict:
    """Weights for every leaf of ``layout`` (a tree of ShapeDtypeStructs):
    dense matrices N(0, 1/d_in), biases and vectors N(0, 0.05^2), the time
    encoder at TGAT's fixed ladder."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)
    paths = [tuple(k.key for k in p) for p, _ in flat]
    shapes = [leaf.shape for _, leaf in flat]

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(paths))
        return [_draw(keys[i], paths[i], shapes[i])
                for i in range(len(paths))]

    return jax.tree_util.tree_unflatten(treedef, draw(key_of(seed)))
