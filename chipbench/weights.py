"""Weights from ``--seed``, made on the device in one jitted call.

A family's reference (``chipbench/reference/<family>.py``) lists its leaves:
``(name, shape, init, scale)`` with the layers stacked on the first axis. The
same list and the same seed give the same bits to the program under test, to
the reference and to a leaf made again on its own (leaf ``i`` draws from
``fold_in(key, i)``, and threefry does not depend on what else is in the call).
"""

from __future__ import annotations

import functools

from chipbench.lib import seed_key

WEIGHT_STREAM = 1


def _leaf(key, index: int, shape, init: str, scale: float, dtype):
    import jax
    import jax.numpy as jnp

    if init == "normal":
        draw = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
        return (draw * scale).astype(dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    raise ValueError(f"unknown init {init!r}")


def make_weights(spec, seed: int, dtype) -> dict:
    """Every leaf of ``spec`` as ``{name: array}``, in one jitted call."""
    import jax

    spec = tuple((n, tuple(s), i, float(c)) for n, s, i, c in spec)

    @jax.jit
    def build(key):
        return {
            name: _leaf(key, index, shape, init, scale, dtype)
            for index, (name, shape, init, scale) in enumerate(spec)
        }

    return build(seed_key(seed, WEIGHT_STREAM))


def distance_from_initial(spec, seed: int, dtype, current: dict) -> dict:
    """``{name: ||current[name] - initial[name]||}`` with each initial leaf made
    again from the seed inside the program that takes the norm, one leaf at a
    time, so that no second copy of the weights is ever held."""
    import jax
    import jax.numpy as jnp

    key = seed_key(seed, WEIGHT_STREAM)

    @functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
    def one(key, leaf, index, shape, init, scale):
        first = _leaf(key, index, shape, init, scale, dtype).astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32) - first)))

    out = {}
    for index, (name, shape, init, scale) in enumerate(spec):
        out[name] = one(key, current[name], index, tuple(shape), init, float(scale))
    return {name: float(v) for name, v in out.items()}


def nest(flat: dict) -> dict:
    """``{"a.b.c": x}`` to ``{"a": {"b": {"c": x}}}``: the leaves are named by
    their path in the program's parameter tree."""
    tree: dict = {}
    for name, value in flat.items():
        node = tree
        parts = name.split(".")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return tree


def flatten(tree, prefix: str = "") -> dict:
    flat = {}
    for key, value in tree.items():
        name = f"{prefix}.{key}" if prefix else key
        if isinstance(value, dict):
            flat.update(flatten(value, name))
        else:
            flat[name] = value
    return flat
