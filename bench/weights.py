"""Random weights, made by the benchmark from the seed, in one jitted call.

The program declares the tree of parameters it serves (``jax.eval_shape``
of its ``init_params``); this module fills that tree on the device, in
the served dtype, from a key of its own.  The program and the plain
reference then read the same arrays, and the reference takes nothing the
program computed.

Distributions, by leaf name, as the program's own ``init_params`` draws
them: a projection ``w`` of shape ``[..., n_in, n_out]`` is normal with
standard deviation ``n_in ** -0.5``; an embedding or output ``table`` is
normal with standard deviation 0.02; a norm ``scale`` is
``1 + 0.1 * normal`` (not ones, so that a norm whose scale were dropped
would show in the comparison); a bias is ``0.02 * normal``.

One departure: the attention output projection ``o`` is scaled by
``ATTN_OUT_SCALE``.  Over prompts of thousands of random tokens, attention
with random weights averages its values into one vector that is nearly
the same at every position, and at full scale the residual stream fills
with it layer after layer: on some seeds every position's logits then
share one top token by a wide margin, greedy decoding serves that token
over and over, and no comparison of served tokens can see a change of
precision or a fault in the KV.  Scaled by 0.35, the shared part stays
small and the served tokens vary and depend on the cached KV (readings in
``PERF.md``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ATTN_OUT_SCALE = 0.35


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key for any whole seed, also beyond 32 bits (``PRNGKey``
    keeps only the low 32 bits without x64)."""
    words = np.random.SeedSequence(seed % 2**64).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf(key, path: tuple[str, ...], sds) -> jax.Array:
    name = path[-1]
    dt = sds.dtype
    z = jax.random.normal(key, sds.shape, dt)
    if name == "w":
        scale = ATTN_OUT_SCALE if path[-2] == "o" else 1.0
        return z * jnp.asarray(scale * sds.shape[-2] ** -0.5, dt)
    if name == "table":
        return z * jnp.asarray(0.02, dt)
    if name == "scale":
        return 1 + z * jnp.asarray(0.1, dt)
    if name == "b":
        return z * jnp.asarray(0.02, dt)
    raise ValueError(f"no rule for parameter {'/'.join(path)}")


def _path(kp) -> tuple[str, ...]:
    return tuple(str(getattr(k, "key", k)) for k in kp)


def make(shapes, seed: int):
    """Fill the declared tree ``shapes`` (ShapeDtypeStructs) on the
    default device, in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(k, _path(kp), sds) for k, (kp, sds) in zip(keys, flat)])

    return jax.jit(fill)(key_from_seed(seed))
