"""Seeded random weights, made on the device in one jitted call.

The tree's layout (leaf names and shapes) is the served program's parameter
interface, read with ``jax.eval_shape`` so no value of the program's own
initialiser is used. Every value is drawn here, from ``--seed``, in the
dtype the configuration serves: norms at 1, NSA pooling logits at 0, the
compression projections near the identity, gate biases at 0, embeddings
N(0, 0.02), every other matrix N(0, 1/fan_in).
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp


def _path_name(path) -> str:
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
    return "/".join(parts)


def _leaf_salt(name: str) -> int:
    return int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "little")


def _draw(key, name: str, shape, dtype):
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "scale":
        return jnp.ones(shape, dtype)
    if leaf in ("phi_k", "phi_v", "b_gate"):
        return jnp.zeros(shape, dtype)
    if leaf in ("w_cmp_k", "w_cmp_v"):
        eye = jnp.eye(shape[-1], dtype=jnp.float32)
        noise = 0.02 * jax.random.normal(key, shape, jnp.float32)
        return (eye + noise).astype(dtype)
    if leaf == "table":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if leaf == "w_gate" and "/mix/" in f"/{name}":
        return (0.01 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    fan_in = shape[-2]
    w = jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
    return w.astype(dtype)


def seed_key(seed: int, salt: int):
    """A PRNG key from a seed of any size (folded in 32-bit words)."""
    key = jax.random.PRNGKey(salt)
    seed = int(seed)
    key = jax.random.fold_in(key, 1 if seed < 0 else 0)
    seed = abs(seed)
    while True:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return key


def make_params(abstract, seed: int, salt: int):
    """Draw a parameter tree shaped like ``abstract`` (a pytree of
    ShapeDtypeStruct) from ``seed``, in one jitted program on the default
    device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [_path_name(p) for p, _ in flat]
    specs = [(tuple(s.shape), s.dtype) for _, s in flat]

    @jax.jit
    def build(key):
        leaves = [_draw(jax.random.fold_in(key, _leaf_salt(n)), n, shape, dt)
                  for n, (shape, dt) in zip(names, specs)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(seed_key(seed, salt))
