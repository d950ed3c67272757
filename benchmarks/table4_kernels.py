"""Table IV analogue. The paper reports FPGA LUT/BRAM/DSP budgets; the
TPU equivalents are per-kernel on-chip (VMEM/SMEM) budgets and DMA
depths, derived from the BlockSpec tiling — plus interpret-mode
correctness timing for scale."""
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed


def vmem_budget():
    rows = []
    # walk_step uniform: SMEM task words + 2-deep DMA buffers
    tile = 256
    smem = tile * 4 * 4 + 2 * 2 * 4 + 2 * 1 * 4   # v,u,out*2 scratch + bufs
    rows.append(("walk_step_uniform", smem, 2))
    tile_e, rb, D = 256, 128, 128
    vmem = tile_e * D * 4 + rb * D * 4 + rb * tile_e * 4
    rows.append(("segment_sum", vmem, 1))
    tb, H, D = 128, 8, 16
    vmem = tb * H * 4 * 2 + D * 4 + 2 * D * 4 + tb * D * 4
    rows.append(("embedding_bag", vmem, 2))
    return rows


def run(quick: bool = False):
    for name, bytes_, dma_depth in vmem_budget():
        emit(f"table4_{name}", 0.0,
             f"onchip_bytes={bytes_};dma_depth={dma_depth};"
             f"vmem_frac={bytes_/128e6:.5f}")
    # interpret-mode validation timing (not TPU perf — correctness gate)
    from repro.graph import make_dataset
    from repro.kernels.common import graph_rows
    from repro.kernels.walk_step import ops as ws
    g = make_dataset("WG", scale_override=10)
    rng = np.random.default_rng(0)
    W = 512
    v = jnp.asarray(rng.integers(0, g.num_vertices, W), jnp.int32)
    u = jnp.asarray(rng.random(W), jnp.float32)
    rows = graph_rows(g)
    dt, _ = timed(lambda: ws.walk_step_uniform(v, u, rows, tile=256))
    emit("table4_walk_step_interpret", dt * 1e6, f"lanes={W}")
    return True


if __name__ == "__main__":
    run()
