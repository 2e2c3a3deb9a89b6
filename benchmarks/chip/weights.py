"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call, in the type they are served in (the configuration's
``torch_dtype``), in the layout that the family's module gives.

The benchmark makes the weights so that the reference never takes anything
the program made. The tree has the program's layout, which ``check_layout``
compares leaf by leaf with the program's own abstract parameters before a
run uses it.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

import families

NORM_SCALE = 0.05   # norms are stored as ``weight - 1``: near 0, not 0


def seed_key(seed: int):
    """A threefry key from any whole number (seeds may exceed 32
    bits), as data: pass it into a jitted call so that every seed runs the
    same compiled program."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(state)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return out


def make_params(cfg: dict, seed: int):
    """The weights of ``cfg`` for ``seed``: normal with standard deviation
    1/sqrt(fan_in) for matrices, ``NORM_SCALE`` for norm scales."""
    layout = families.of(cfg).layout(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])

    def init(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        flat = {}
        for path, (shape, fan_in) in layout.items():
            k = jax.random.fold_in(key, zlib.crc32(path.encode()))
            scale = NORM_SCALE if fan_in is None else 1.0 / np.sqrt(fan_in)
            flat[path] = (scale * jax.random.normal(k, shape,
                                                    jnp.float32)).astype(dtype)
        return _nest(flat)

    # dalek: allow[bare-jit] the benchmark's weight maker, not the system
    params = jax.jit(init)(seed_key(seed))
    jax.block_until_ready(params)
    return params


def check_layout(params, abstract) -> None:
    """Raise unless ``params`` has the structure, shapes and dtypes of the
    program's abstract parameters."""
    a = jax.tree_util.tree_flatten_with_path(abstract)[0]
    p = jax.tree_util.tree_flatten_with_path(params)[0]
    got = {jax.tree_util.keystr(k): (tuple(x.shape), str(x.dtype)) for k, x in p}
    want = {jax.tree_util.keystr(k): (tuple(x.shape), str(x.dtype))
            for k, x in a}
    if got != want:
        raise ValueError(f"benchmark weight layout differs from the "
                         f"program's: missing {sorted(set(want) - set(got))}, "
                         f"extra {sorted(set(got) - set(want))}, differing "
                         f"{[k for k in got if k in want and got[k] != want[k]]}")
