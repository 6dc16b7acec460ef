"""Paper Fig. 14: dynamic threshold vs fixed thresholds on PageRank.

Claims: (a) the dynamic policy beats every fixed theta; (b) theta adapts
over the run (trace recorded); (c) bandwidth responds to promotions.
"""
from __future__ import annotations

from repro.core.simulator import WORKLOADS, run_sim

from benchmarks.common import BLOCK, FAST_RATIO, N_BLOCKS, N_PAGES, SIM_KW, Timer, emit

FIXED = [2, 8, 32, 128]


def run(quick: bool = False):
    n_blocks = N_BLOCKS // 4 if quick else N_BLOCKS

    def sim(theta=None):
        stream = WORKLOADS["pagerank"](n_pages=N_PAGES, block=BLOCK,
                                       n_blocks=n_blocks, seed=41)
        return run_sim("neomem", stream, n_pages=N_PAGES,
                       fast_ratio=FAST_RATIO, fixed_theta=theta,
                       collect_trace=True, **SIM_KW)

    with Timer() as t:
        dyn = sim(None)
        emit("fig14_dynamic", t.s * 1e6,
             f"runtime_ms={dyn.runtime*1e3:.2f} hit={dyn.hit_rate:.3f}")
        for th in FIXED:
            r = sim(th)
            emit(f"fig14_fixed_theta{th}", 0.0,
                 f"runtime_ms={r.runtime*1e3:.2f} hit={r.hit_rate:.3f} "
                 f"vs_dynamic={r.runtime/dyn.runtime:.2f}x")
    thetas = [tr["theta"] for tr in dyn.trace]
    bws = [f"{tr['bw']:.2f}" for tr in dyn.trace]
    emit("fig14_theta_trace", 0.0, " ".join(map(str, thetas[:32])))
    emit("fig14_bw_trace", 0.0, " ".join(bws[:32]))


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run()
