"""Paper Fig. 16: convergence after a hot-set shift (GUPS).

Claims: NeoMem holds the highest steady-state rate, converges fastest after
the shift; baselines recover slower / noisier.
"""
from __future__ import annotations

import numpy as np

from repro.core.simulator import WORKLOADS, run_sim

from benchmarks.common import BLOCK, FAST_RATIO, N_BLOCKS, N_PAGES, SIM_KW, Timer, emit


def run(quick: bool = False):
    n_blocks = 160 if quick else 320
    shift = n_blocks // 2
    with Timer() as t:
        for m in ("neomem", "pebs", "tpp", "pte-scan"):
            stream = WORKLOADS["gups"](n_pages=N_PAGES, block=BLOCK,
                                       n_blocks=n_blocks, seed=61,
                                       shift_at=shift)
            r = run_sim(m, stream, n_pages=N_PAGES, fast_ratio=FAST_RATIO,
                        collect_trace=True, **SIM_KW)
            # trace hit_rate is cumulative; convert to per-period rates
            tot = [tr["hit_rate"] * (i + 1) for i, tr in enumerate(r.trace)]
            per = [tot[0]] + [tot[i] - tot[i - 1] for i in range(1, len(tot))]
            n = len(per)
            pre = float(np.mean(per[n // 2 - 4:n // 2]))
            post = float(np.mean(per[-4:]))
            dip = float(min(per[n // 2:n // 2 + 4])) if n > 4 else 0.0
            # recovery: periods after the shift until within 90% of pre rate
            rec = next((i for i, h in enumerate(per[n // 2:])
                        if h >= 0.9 * pre), n // 2)
            emit(f"fig16_{m}", t.s * 1e6 / 4,
                 f"pre_shift_hit={pre:.3f} dip={dip:.3f} post_hit={post:.3f} "
                 f"recovery_periods={rec}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run()
