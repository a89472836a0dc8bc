"""Random weights from the seed, made on the device in one jitted call, in
the type they are served in and in the parameter layout the serving
engine reads (the architecture module's ``shapes``).  The reference
reads the same arrays; neither side makes them."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def weight_seed(c: dict, seed: int) -> int:
    """The seed the weights are drawn from.  A configuration whose serving
    weights are built and kept per weight set (the encoded bundle, 3.3 GB
    on disk) draws them from ``seed % weight_seeds``: with 1, every run
    serves one weight set and only a checkout's first run builds it;
    prompts and arrivals still follow the whole seed."""
    n = c.get("weight_seeds")
    return int(seed) % int(n) if n else int(seed)


def _scale(path: str, shape: tuple) -> tuple:
    """(mean, std) of a leaf: fan-in scaled matrices, small biases and an
    embedding of std 0.02, norm gains around 1."""
    name = path.split("/")[-1]
    if name == "table":
        return 0.0, 0.02
    if name.endswith("_g"):
        return 1.0, 0.1
    if name.endswith("_b"):
        return 0.0, 0.02
    return 0.0, shape[-2] ** -0.5


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, frozen_shapes, dtype):
    leaves = []
    for i, (path, shape) in enumerate(frozen_shapes):
        mean, std = _scale(path, shape)
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        leaves.append((mean + std * x).astype(dtype))
    return leaves


def _flat(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _flat(v, f"{prefix}{k}/")
        else:
            yield f"{prefix}{k}", tuple(v)


def make_params(arch, c: dict, seed: int) -> dict:
    """The configuration's weights for ``seed`` as a nested dict of device
    arrays in ``c['torch_dtype']``, in the leaf shapes that its
    architecture module ``arch`` gives (``arch.shapes(c)``)."""
    flat = tuple(_flat(arch.shapes(c)))
    leaves = _make(seed_key(seed), flat, jnp.dtype(c["torch_dtype"]))
    out: dict = {}
    for (path, _), leaf in zip(flat, leaves):
        node = out
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return out
