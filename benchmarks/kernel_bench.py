"""Kernel microbenchmarks (paper Table III analogue): NeoProf throughput.

Interpret-mode wall times are NOT TPU times; reported for relative tracking.
Also reports the sketch's modeled VMEM footprint per segment tile.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sketch import SketchParams, sketch_init
from repro.core import sketch as sk
from repro.kernels.neoprof_update import ops as kops

from benchmarks.common import emit


def run(quick: bool = False):
    sp = SketchParams(width=1 << 14, depth=2)
    st = sketch_init(sp)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 1 << 20, 2048).astype(np.int32))

    # pure-jax reference path (the production CPU-fallback)
    f = jax.jit(lambda s, i: sk.sketch_update(s, i, jnp.int32(64), sp))
    f(st, ids)[0].counts.block_until_ready()
    n = 3 if quick else 10
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(st, ids)
    out[0].counts.block_until_ready()
    dt = (time.perf_counter() - t0) / n
    emit("neoprof_update_jax", dt * 1e6,
         f"{2048/dt/1e6:.1f}M pages/s (CPU jit; W=16K D=2)")

    # Pallas interpret path (correctness harness, not perf)
    g = jax.jit(lambda s, i: kops.sketch_update(s, i, jnp.int32(64), sp,
                                                interpret=True))
    g(st, ids)[0].counts.block_until_ready()
    t0 = time.perf_counter()
    out = g(st, ids)
    out[0].counts.block_until_ready()
    dt = time.perf_counter() - t0
    emit("neoprof_update_pallas_interpret", dt * 1e6,
         "interpret-mode (correctness only)")

    # modeled TPU VMEM footprint per grid step
    seg = 512
    vmem = (sp.depth * seg * 4 * 3        # counts/epochs/hot blocks
            + 2048 * 4                      # stream ids
            + sp.depth * 2048 * 4 * 2)      # est/hot_before accumulators
    emit("neoprof_update_vmem_per_tile", 0.0, f"{vmem/1024:.0f} KiB (seg=512)")
    emit("sketch_sram_total", 0.0,
         f"{sp.depth*sp.width*2/1024:.0f} KiB counter array "
         f"(paper ASIC: 512K x 16b x 2 = 2 MiB)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run()
