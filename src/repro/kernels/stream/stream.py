"""STREAM-suite Pallas kernels (paper Fig. 4 / Sec. 5.1 `bandwidth`).

The paper's bandwidth benchmark measures read/write/copy/scale/add/triad
across the memory hierarchy. On TPU the hierarchy is HBM -> VMEM -> VREG;
these kernels stream HBM-resident buffers through VMEM tiles (BlockSpec)
exactly like the paper's explicitly vectorized loops stream through cache
lines (non-temporal stores map to the one-pass VMEM write-back).

Grid: 1-D over row blocks; each program handles a (block_rows, cols) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _copy_kernel(a_ref, o_ref):
    o_ref[...] = a_ref[...]


def _scale_kernel(x_scalar_ref, a_ref, o_ref):
    o_ref[...] = a_ref[...] * x_scalar_ref[0]


def _add_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] + b_ref[...]


def _triad_kernel(x_scalar_ref, a_ref, b_ref, o_ref):
    o_ref[...] = x_scalar_ref[0] * a_ref[...] + b_ref[...]


def _write_kernel(x_scalar_ref, o_ref):
    o_ref[...] = jnp.full_like(o_ref, x_scalar_ref[0])


def _read_kernel(a_ref, o_ref):
    # fold the tile into one (8, cols) slab of partial sums with vector adds
    # only: reads the whole stream, writes one sublane group per tile
    acc = a_ref[0:8, :]
    for r in range(8, a_ref.shape[0], 8):
        acc = acc + a_ref[r:r + 8, :]
    o_ref[...] = acc


def _blocks(shape, block_rows):
    rows, cols = shape
    block_rows = min(block_rows, rows)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    return grid, spec


def stream_copy(a, *, block_rows=256, interpret=False):
    grid, spec = _blocks(a.shape, block_rows)
    return pl.pallas_call(
        _copy_kernel, grid=grid, in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret)(a)


def stream_scale(a, x, *, block_rows=256, interpret=False):
    grid, spec = _blocks(a.shape, block_rows)
    xs = jnp.asarray([x], a.dtype)
    return pl.pallas_call(
        _scale_kernel, grid=grid,
        in_specs=[pl.BlockSpec((1,), lambda i: (0,)), spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret)(xs, a)


def stream_add(a, b, *, block_rows=256, interpret=False):
    grid, spec = _blocks(a.shape, block_rows)
    return pl.pallas_call(
        _add_kernel, grid=grid, in_specs=[spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret)(a, b)


def stream_triad(a, b, x, *, block_rows=256, interpret=False):
    grid, spec = _blocks(a.shape, block_rows)
    xs = jnp.asarray([x], a.dtype)
    return pl.pallas_call(
        _triad_kernel, grid=grid,
        in_specs=[pl.BlockSpec((1,), lambda i: (0,)), spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret)(xs, a, b)


def stream_write(shape, x, dtype=jnp.float32, *, block_rows=256,
                 interpret=False):
    grid, spec = _blocks(shape, block_rows)
    xs = jnp.asarray([x], dtype)
    return pl.pallas_call(
        _write_kernel, grid=grid,
        in_specs=[pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=interpret)(xs)


def stream_read(a, *, block_rows=256, interpret=False):
    """Per-tile sums, [rows // block_rows, 1]. The kernel's output block is
    (8, cols), since the TPU compiler needs the last two block dims to be
    multiples of (8, 128) or the array's own; the slabs are summed here."""
    rows, cols = a.shape
    block_rows = min(block_rows, rows)
    if block_rows % 8:
        raise ValueError(f"block_rows must be a multiple of 8, got {block_rows}")
    grid = (rows // block_rows,)
    slabs = pl.pallas_call(
        _read_kernel, grid=grid,
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0] * 8, cols), a.dtype),
        interpret=interpret)(a)
    return slabs.reshape(grid[0], 8 * cols).sum(axis=1, keepdims=True)
