"""Pipeline parallelism (GPipe schedule) via shard_map + ppermute.

For models beyond single-pod HBM, an optional `pipe` mesh axis splits the
layer stack into stages; microbatches stream through with collective
permutes between stages.  Bubble fraction = (P-1)/(M+P-1) — the classic
GPipe result; the launcher picks M >= 4·P so the bubble stays under 20%.

This is demonstrated/tested at small scale (8 host devices) and available
as a config knob; the 16×16 production mesh fits all assigned archs
without PP (see EXPERIMENTS.md §Dry-run memory numbers).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_apply(stage_fn: Callable, params_stacked, x_microbatches,
                   mesh, axis: str = "pipe"):
    """Run x through P stages living on the `pipe` axis.

    stage_fn(stage_params, x) -> x  (one stage's compute)
    params_stacked: pytree with leading stage axis (P, ...)
    x_microbatches: (M, mb, ...) microbatched input.
    Returns (M, mb, ...) outputs (after all P stages).
    """
    n_stages = mesh.shape[axis]

    def body(stage_params, xs):
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
        rank = jax.lax.axis_index(axis)
        M = xs.shape[0]
        T = M + n_stages - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def step(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any); others use the
            # permuted activation from the previous stage.
            mb_idx = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(rank == 0,
                             xs[mb_idx],
                             buf)
            y = stage_fn(stage_params, x_in)
            # forward to the next stage (ring shift by +1)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            # last stage emits microbatch t - (P-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            emit = (t >= n_stages - 1) & (rank == n_stages - 1)
            outs = jnp.where(emit,
                             outs.at[out_idx].set(y),
                             outs)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, T, step, (buf, outs))
        return outs[None]

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(None)),
                       out_specs=P(axis), check_vma=False)
    outs = fn(params_stacked, x_microbatches)
    # every stage returns a buffer; only the last stage's is valid
    return outs[-1]


def gpipe_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
