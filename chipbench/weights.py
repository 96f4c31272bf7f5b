"""Weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights itself, in the layout the program's
parameter tree has (`shapes`, from `jax.eval_shape` of the program's
init) and in the dtype they are served in, and hands the same arrays to
the program and to the plain reference. Each leaf is drawn by its name:

  embed, lm_head      N(0, 0.02) / N(0, sqrt(2 / (in + out))); rows or
                      columns past the real vocabulary are zero, as a
                      checkpoint padded to the program's vocabulary is
  layer matrices      N(0, sqrt(2 / (in + out))), one layer at a time
  norm weights        1 + N(0, 0.05)
  biases              N(0, 0.02)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, path: str, shape, dtype, vocab: int):
    name = path.split("/")[-1]
    if name == "embed":
        w = jax.random.normal(key, shape, dtype) * 0.02
        keep = jnp.arange(shape[0])[:, None] < vocab
        return jnp.where(keep, w, 0).astype(dtype)
    if name == "lm_head":
        w = jax.random.normal(key, shape, dtype) \
            * (2.0 / sum(shape)) ** 0.5
        keep = jnp.arange(shape[1])[None, :] < vocab
        return jnp.where(keep, w, 0).astype(dtype)
    if name == "w" and len(shape) <= 2:          # norm weights
        return (1.0 + 0.05 * jax.random.normal(key, shape)).astype(dtype)
    if name.startswith("b"):                     # biases
        return (0.02 * jax.random.normal(key, shape)).astype(dtype)
    if len(shape) == 3:                          # (L, in, out) matrices
        scale = (2.0 / (shape[1] + shape[2])) ** 0.5
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape[1:], dtype)
                       * scale).astype(dtype),
            jax.random.split(key, shape[0]))
    raise ValueError(f"no rule for weight {path} {shape}")


def make_weights(shapes, seed: int, vocab: int, device=None):
    """All leaves of the tree `shapes` (ShapeDtypeStructs), drawn from
    `seed` in one jitted call on `device`."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in leaves]
    specs = [(tuple(s.shape), s.dtype) for _, s in leaves]

    def build(key):
        out = []
        for i, (path, (shape, dtype)) in enumerate(zip(paths, specs,
                                                       strict=True)):
            out.append(_leaf(jax.random.fold_in(key, i), path, shape,
                             dtype, vocab))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)
