"""Jitted public wrappers for the fused walk-step kernel.

Pads the lane count to a tile multiple, dispatches to the Pallas kernel,
and exposes the jnp reference behind an explicit ``use_kernel=False``.
The default tile is 1024 lanes: on a TPU a 1-D int32 array in HBM is laid
out in 1024-word tiles, and Mosaic refuses an SMEM block of it that does
not match (a tile equal to the whole lane count is always accepted).  ``interpret``
defaults to ``jax.default_backend() != "tpu"``: the kernel compiles on a
real TPU and interprets its body elsewhere (CPU CI) — override per call
to force either.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import GraphRows, default_interpret, flat_words
from repro.kernels.walk_step import ref as _ref, walk_step as _k


def _flat(rows):
    """(row_ptr, col) of ``rows`` as the 1-D arrays the reference takes."""
    return (flat_words(rows.row_ptr, rows.num_vertices + 1),
            flat_words(rows.col, rows.num_edges))


def _pad_to(x, n, fill):
    w = x.shape[0]
    if w == n:
        return x
    return jnp.concatenate([x, jnp.full((n - w,), fill, x.dtype)])


@partial(jax.jit, static_argnames=("tile", "interpret", "use_kernel"))
def walk_step_uniform(v_curr, u_col, rows: GraphRows, tile: int = 1024,
                      interpret: bool | None = None, use_kernel: bool = True):
    """(v_next, deg) for each lane; ``rows`` from `kernels.common.graph_rows`."""
    interpret = default_interpret(interpret)
    if not use_kernel:
        return _ref.walk_step_uniform_ref(v_curr, u_col, *_flat(rows))
    W = v_curr.shape[0]
    t = min(tile, W)
    Wp = -(-W // t) * t
    vn, dg = _k.walk_step_uniform(
        _pad_to(v_curr, Wp, 0), _pad_to(u_col, Wp, 0.0), rows,
        tile=t, interpret=interpret)
    return vn[:W], dg[:W]


@partial(jax.jit, static_argnames=("tile", "interpret", "use_kernel"))
def walk_step_alias(v_curr, u_col, u_acc, rows: GraphRows, tile: int = 1024,
                    interpret: bool | None = None, use_kernel: bool = True):
    """Alias-table variant of :func:`walk_step_uniform`."""
    interpret = default_interpret(interpret)
    if not use_kernel:
        ne = rows.num_edges
        return _ref.walk_step_alias_ref(
            v_curr, u_col, u_acc, *_flat(rows),
            flat_words(rows.alias_prob, ne), flat_words(rows.alias_idx, ne))
    W = v_curr.shape[0]
    t = min(tile, W)
    Wp = -(-W // t) * t
    vn, dg = _k.walk_step_alias(
        _pad_to(v_curr, Wp, 0), _pad_to(u_col, Wp, 0.0),
        _pad_to(u_acc, Wp, 0.0), rows, tile=t, interpret=interpret)
    return vn[:W], dg[:W]
