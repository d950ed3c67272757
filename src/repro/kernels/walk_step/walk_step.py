"""Fused walk-step Pallas TPU kernel — the paper's asynchronous pipeline
(§V-B) as one kernel: Row Access → Sampling → Column Access.

TPU adaptation of the asynchronous memory-access engine:
  * ``row_ptr`` / ``col`` (and alias tables) live in HBM (`pl.ANY`) as
    128-lane rows (`kernels.common.graph_rows`, built once per run: the
    chip's DMA engine moves whole rows, not single words); the kernel issues **double-buffered
    async DMAs** per task — the copy for
    task *i+1* is in flight while task *i* is processed, which is exactly
    the paper's non-blocking outstanding-request scheme (scaled to the
    DMA depth Pallas exposes; the FPGA engine keeps 128 in flight, a TPU
    core hides latency with the same overlap via its DMA queues).
  * Row access loads ``row_ptr[v]`` and ``row_ptr[v+1]`` in ONE DMA of
    the two rows that can hold them (the paper's RP_entry packs
    address+degree in one word).
  * Sampling arithmetic (uniform or alias) runs on scalars in SMEM between
    the two gather stages, so intermediates never round-trip to HBM.
  * Task words (v_curr, uniforms) are staged in SMEM — the analogue of the
    single-pipeline-word task tuple of §V-A.

Grid: one program per tile of ``TILE`` walker lanes; lanes are fully
independent (stateless tasks), so tiles can execute in any order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, ScheduleBuilder


def dma_schedule(kind: str = "uniform", n: int = 3):
    """Declarative DMA schedule of the one-hop walk-step kernel, for the
    static hazard analyzer (`repro.analysis.dma_hazards`).

    Mirrors the kernel bodies below op-for-op: the uniform kernel runs
    the row-access pair gather then the column gather; the alias kernel
    adds the prob/alias probe loops between them.  ``n`` lanes of unroll
    (≥ 3 covers both slot parities of the double buffer plus prologue
    and drain — the pipelines are period-2 in the slot cycle).  Keep in
    sync with `walk_step_uniform_kernel` / `walk_step_alias_kernel`.
    """
    b = ScheduleBuilder()
    b.gather_loop("rpbuf", n)            # row_access_loop
    if kind == "alias":
        b.gather_loop("probbuf", n)      # accept-probability probes
        b.gather_loop("aliasbuf", n)     # alias-index probes
    b.gather_loop("colbuf", n)           # column access
    return b.ops


def _rows_copy(src_rows, e, nrows, buf, sem, slot):
    """Async copy of the ``nrows`` 128-lane rows starting at the row that
    holds flat word ``e`` of a row-tiled array (`kernels.common.graph_rows`)
    into staging slot ``slot``."""
    return pltpu.make_async_copy(src_rows.at[pl.ds(e // LANES, nrows)],
                                 buf.at[slot], sem.at[slot])


def _word(buf, slot, off):
    """Word ``off`` (counted from the first staged row) of slot ``slot``."""
    return buf[slot, off // LANES, off % LANES]


def gather2_loop(n, f_fn, src_rows, buf, sem, on_result):
    """Double-buffered packed-pair gather: ``buf[slot]`` (two rows) gets
    the rows holding flat words ``f_fn(i)`` and ``f_fn(i) + 1`` of the
    row-tiled ``src_rows`` — an RP_entry or a ``type_offsets[v, t:t+2]``
    sub-segment bound — with item i+1's fetch in flight while item i is
    consumed.  Calls on_result(i, first, second).  Shared with the fused
    superstep kernel (`kernels/fused_superstep`)."""

    def copy(i, slot):
        return _rows_copy(src_rows, f_fn(i), 2, buf, sem, slot)

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n)
        def _():
            copy(i + 1, jax.lax.rem(i + 1, 2)).start()

        copy(i, slot).wait()
        off = jax.lax.rem(f_fn(i), LANES)
        on_result(i, _word(buf, slot, off), _word(buf, slot, off + 1))
        return 0

    copy(0, 0).start()
    jax.lax.fori_loop(0, n, body, 0, unroll=False)


def row_access_loop(n, v_fn, rp_rows, rpbuf, rpsem, num_vertices,
                    on_result):
    """Double-buffered RP_entry loop over lanes: the pair (row_ptr[v],
    row_ptr[v+1]) for v = v_fn(i) — the paper's packed RP_entry, with lane
    i+1's fetch in flight while lane i is consumed.  Calls
    on_result(i, addr, deg).  Shared with the fused superstep kernel."""

    def f(i):
        return jnp.clip(v_fn(i), 0, num_vertices - 1)

    gather2_loop(n, f, rp_rows, rpbuf, rpsem,
                 lambda i, lo, hi: on_result(i, lo, hi - lo))


def gather1_loop(n, e_fn, src_rows, buf, sem, num_entries, on_result):
    """Double-buffered one-word gather: buf[slot] gets the row holding
    ``src[e_fn(i)]`` of the row-tiled ``src_rows``.  Shared with the fused
    superstep kernel."""

    def e_of(i):
        return jnp.clip(e_fn(i), 0, num_entries - 1)

    def copy(i, slot):
        return _rows_copy(src_rows, e_of(i), 1, buf, sem, slot)

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n)
        def _():
            copy(i + 1, jax.lax.rem(i + 1, 2)).start()

        copy(i, slot).wait()
        on_result(i, _word(buf, slot, jax.lax.rem(e_of(i), LANES)))
        return 0

    copy(0, 0).start()
    jax.lax.fori_loop(0, n, body, 0, unroll=False)


def cached_gather1_loop(n, e_fn, src_rows, buf, sem, num_entries, on_result,
                        *, cache_ref=None, cache_len=1, cache_e_fn=None,
                        hit_fn=None):
    """Skew-aware variant of :func:`gather1_loop`: items whose lane holds
    a hot vertex (``hit_fn(i)``) are served straight from the SMEM cache
    block ``cache_ref`` (no copy, no wait — the same bytes at
    ``cache_e_fn(i)``), while misses run the standard double-buffered HBM
    loop.  Both the prefetch for item i+1 and the wait for item i are
    predicated on that item actually missing, so a fully-hit pass issues
    zero DMAs; results are bit-identical either way because the cache
    packs verbatim CSR slices.  With ``hit_fn=None`` (cache off) this IS
    `gather1_loop` — the uncached kernel trace is unchanged."""
    if hit_fn is None or cache_ref is None:
        return gather1_loop(n, e_fn, src_rows, buf, sem, num_entries,
                            on_result)
    cache_e_fn = cache_e_fn or e_fn

    def hit(i):
        # Lookahead may probe index n; clamp — the predicate it feeds is
        # already false there.
        return hit_fn(jnp.minimum(i, n - 1))

    def e_of(i):
        return jnp.clip(e_fn(i), 0, num_entries - 1)

    def copy(i, slot):
        return _rows_copy(src_rows, e_of(i), 1, buf, sem, slot)

    def body(i, _):
        slot = jax.lax.rem(i, 2)
        h = hit_fn(i)

        @pl.when((i + 1 < n) & jnp.logical_not(hit(i + 1)))
        def _():
            copy(i + 1, jax.lax.rem(i + 1, 2)).start()

        @pl.when(jnp.logical_not(h))
        def _():
            copy(i, slot).wait()

        ce = jnp.clip(cache_e_fn(i), 0, cache_len - 1)
        # Hit lanes never started a copy: buf holds a stale value the
        # where() discards.
        on_result(i, jnp.where(h, cache_ref[ce],
                               _word(buf, slot, jax.lax.rem(e_of(i), LANES))))
        return 0

    @pl.when(jnp.logical_not(hit_fn(0)))
    def _():
        copy(0, 0).start()

    jax.lax.fori_loop(0, n, body, 0, unroll=False)


def cached_gather2_loop(n, f_fn, src_rows, buf, sem, on_result, *,
                        hit_fn=None, hit_pair_fn=None):
    """Skew-aware variant of :func:`gather2_loop`: hit items take their
    word pair from ``hit_pair_fn(i)`` (an SMEM cache read) instead of the
    DMA staging buffer, with the same miss-predicated prefetch/wait
    structure as :func:`cached_gather1_loop`.  ``hit_fn=None`` falls back
    to the plain loop."""
    if hit_fn is None or hit_pair_fn is None:
        return gather2_loop(n, f_fn, src_rows, buf, sem, on_result)

    def copy(i, slot):
        return _rows_copy(src_rows, f_fn(i), 2, buf, sem, slot)

    def body(i, _):
        slot = jax.lax.rem(i, 2)
        h = hit_fn(i)

        @pl.when((i + 1 < n) & jnp.logical_not(
            hit_fn(jnp.minimum(i + 1, n - 1))))
        def _():
            copy(i + 1, jax.lax.rem(i + 1, 2)).start()

        @pl.when(jnp.logical_not(h))
        def _():
            copy(i, slot).wait()

        ca, cb = hit_pair_fn(i)
        off = jax.lax.rem(f_fn(i), LANES)
        on_result(i, jnp.where(h, ca, _word(buf, slot, off)),
                  jnp.where(h, cb, _word(buf, slot, off + 1)))
        return 0

    @pl.when(jnp.logical_not(hit_fn(0)))
    def _():
        copy(0, 0).start()

    jax.lax.fori_loop(0, n, body, 0, unroll=False)


def _uniform_index(deg, u):
    idx = jnp.floor(u * deg.astype(jnp.float32)).astype(jnp.int32)
    return jnp.clip(idx, 0, jnp.maximum(deg - 1, 0))


def walk_step_uniform_kernel(num_vertices, num_edges,
                             v_ref, ucol_ref,          # SMEM tiles
                             rp_ref, col_ref,          # ANY (HBM)
                             vnext_ref, deg_ref,       # SMEM outputs
                             addr_scr, idx_scr, rpbuf, colbuf,
                             rpsem, colsem):
    n = v_ref.shape[0]

    def on_row(i, addr, deg):
        addr_scr[i] = addr
        deg_ref[i] = deg
        idx_scr[i] = addr + _uniform_index(deg, ucol_ref[i])

    row_access_loop(n, lambda i: v_ref[i], rp_ref, rpbuf, rpsem,
                    num_vertices, on_row)

    def on_col(i, v):
        vnext_ref[i] = jnp.where(deg_ref[i] > 0, v, -1)

    gather1_loop(n, lambda i: idx_scr[i], col_ref, colbuf, colsem, num_edges, on_col)


def walk_step_alias_kernel(num_vertices, num_edges,
                           v_ref, ucol_ref, uacc_ref,
                           rp_ref, col_ref, prob_ref, alias_ref,
                           vnext_ref, deg_ref,
                           addr_scr, k_scr, idx_scr,
                           rpbuf, probbuf, aliasbuf, colbuf,
                           rpsem, probsem, aliassem, colsem):
    """Alias-table variant (DeepWalk): column draw k, accept test against
    prob[addr+k], fall back to alias[addr+k]. Two extra gathers."""
    n = v_ref.shape[0]

    def on_row(i, addr, deg):
        addr_scr[i] = addr
        deg_ref[i] = deg
        k_scr[i] = addr + _uniform_index(deg, ucol_ref[i])

    row_access_loop(n, lambda i: v_ref[i], rp_ref, rpbuf, rpsem,
                    num_vertices, on_row)

    def on_prob(i, p):
        # accept -> keep k; reject -> need alias[addr+k] (resolved below)
        idx_scr[i] = jnp.where(uacc_ref[i] < p, k_scr[i], -1)

    gather1_loop(n, lambda i: k_scr[i], prob_ref, probbuf, probsem, num_edges, on_prob)

    def on_alias(i, a):
        addr = addr_scr[i]
        take_alias = idx_scr[i] < 0
        idx_scr[i] = jnp.where(take_alias, addr + a, idx_scr[i])

    gather1_loop(n, lambda i: k_scr[i], alias_ref, aliasbuf, aliassem, num_edges, on_alias)

    def on_col(i, v):
        vnext_ref[i] = jnp.where(deg_ref[i] > 0, v, -1)

    gather1_loop(n, lambda i: idx_scr[i], col_ref, colbuf, colsem, num_edges, on_col)


def _smem_tile(tile):
    return pl.BlockSpec((tile,), lambda t: (t,), memory_space=pltpu.SMEM)


def walk_step_uniform(v_curr, u_col, rows, *, tile: int = 1024,
                      interpret: bool = True):
    """pallas_call wrapper: (v_next, deg) for a batch of walker lanes;
    ``rows`` is the graph's `kernels.common.GraphRows`."""
    W = v_curr.shape[0]
    tile = min(tile, W)
    assert W % tile == 0, (W, tile)
    kernel = functools.partial(walk_step_uniform_kernel, rows.num_vertices,
                               rows.num_edges)
    return pl.pallas_call(
        kernel,
        grid=(W // tile,),
        in_specs=[_smem_tile(tile), _smem_tile(tile),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[_smem_tile(tile), _smem_tile(tile)],
        out_shape=[jax.ShapeDtypeStruct((W,), jnp.int32),
                   jax.ShapeDtypeStruct((W,), jnp.int32)],
        scratch_shapes=[pltpu.SMEM((tile,), jnp.int32),
                        pltpu.SMEM((tile,), jnp.int32),
                        pltpu.SMEM((2, 2, LANES), jnp.int32),
                        pltpu.SMEM((2, 1, LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(v_curr, u_col, rows.row_ptr, rows.col)


def walk_step_alias(v_curr, u_col, u_acc, rows, *, tile: int = 1024,
                    interpret: bool = True):
    W = v_curr.shape[0]
    tile = min(tile, W)
    assert W % tile == 0, (W, tile)
    kernel = functools.partial(walk_step_alias_kernel, rows.num_vertices,
                               rows.num_edges)
    return pl.pallas_call(
        kernel,
        grid=(W // tile,),
        in_specs=[_smem_tile(tile), _smem_tile(tile), _smem_tile(tile),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[_smem_tile(tile), _smem_tile(tile)],
        out_shape=[jax.ShapeDtypeStruct((W,), jnp.int32),
                   jax.ShapeDtypeStruct((W,), jnp.int32)],
        scratch_shapes=[pltpu.SMEM((tile,), jnp.int32),
                        pltpu.SMEM((tile,), jnp.int32),
                        pltpu.SMEM((tile,), jnp.int32),
                        pltpu.SMEM((2, 2, LANES), jnp.int32),
                        pltpu.SMEM((2, 1, LANES), jnp.float32),
                        pltpu.SMEM((2, 1, LANES), jnp.int32),
                        pltpu.SMEM((2, 1, LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(v_curr, u_col, u_acc, rows.row_ptr, rows.col, rows.alias_prob,
      rows.alias_idx)
