"""Paper Fig. 4: profiling-mechanism analysis.

(a) PTE-scan time/space-resolution vs overhead frontier against the NeoProf
    point (hot-set recall vs modeled overhead);
(b) TLB-proxy vs true-access dispersion: correlation between per-page
    first-touch epochs counts (what PTE-scan sees) and true access counts;
(c) PEBS sampling-rate vs overhead + recall curve.
"""
from __future__ import annotations

import numpy as np

from repro.core.baselines import BaselineCosts, PebsSampler, PteScan
from repro.core.neoprof import NeoProfCommands, NeoProfParams, neoprof_init, neoprof_observe
from repro.core.sketch import SketchParams
from repro.core.simulator import WORKLOADS

from benchmarks.common import BLOCK, N_PAGES, Timer, emit


def _hot_set(n_pages):
    return set(range(n_pages - n_pages // 10, n_pages))


def _recall(detected, hot):
    return len(set(map(int, detected)) & hot) / max(len(hot), 1)


def run(quick: bool = False):
    n_blocks = 24 if quick else 48
    hot = _hot_set(N_PAGES)
    costs = BaselineCosts()

    # (a) PTE-scan frontier: scan period in blocks (time resolution)
    with Timer() as t:
        for period in (2, 8, 32):
            ps = PteScan(N_PAGES, 0, hot_after_epochs=2)
            stream = WORKLOADS["gups"](n_pages=N_PAGES, block=BLOCK,
                                       n_blocks=n_blocks, seed=4)
            det: set = set()
            for b, pages in enumerate(stream):
                ps.observe(pages)
                if (b + 1) % period == 0:
                    det |= set(ps.epoch_end().tolist())
            emit(f"fig04a_ptescan_period{period}", t.s * 1e6,
                 f"recall={_recall(det, hot):.2f} overhead_ms="
                 f"{ps.overhead*1e3:.2f}")

    # NeoProf point: full recall at ~0 overhead
    pp = NeoProfParams(sketch=SketchParams(width=1 << 12))
    prof = neoprof_init(pp)
    cmd = NeoProfCommands(pp)
    prof = cmd.set_threshold(prof, 16)
    det = set()
    import jax.numpy as jnp
    stream = WORKLOADS["gups"](n_pages=N_PAGES, block=BLOCK,
                               n_blocks=n_blocks, seed=4)
    n_reads = 0
    for pages in stream:
        prof = neoprof_observe(prof, jnp.asarray(pages.astype(np.int32)), pp)
        prof, hotpages = cmd.drain_hotpages(prof)
        det |= set(hotpages.tolist())
        n_reads += 1
    emit("fig04a_neoprof", 0.0,
         f"recall={_recall(det, hot):.2f} overhead_ms="
         f"{n_reads*costs.neoprof_readout*1e3:.3f}")

    # (b) TLB-proxy dispersion: epoch-binary counts vs true counts
    stream = WORKLOADS["silo"](n_pages=N_PAGES, block=BLOCK,
                               n_blocks=n_blocks, seed=5)
    true = np.zeros(N_PAGES)
    tlbish = np.zeros(N_PAGES)
    seen_this_epoch = np.zeros(N_PAGES, bool)
    for b, pages in enumerate(stream):
        np.add.at(true, pages, 1)
        first = ~seen_this_epoch[pages]
        tlbish[pages[first]] += 1
        seen_this_epoch[pages] = True
        if (b + 1) % 8 == 0:
            seen_this_epoch[:] = False
    mask = true > 0
    corr = np.corrcoef(true[mask], tlbish[mask])[0, 1]
    emit("fig04b_tlb_vs_llc_corr", 0.0,
         f"pearson={corr:.2f} (paper: high dispersion => weak proxy)")

    # (c) PEBS: rate vs overhead + recall
    for interval in (10, 100, 1000, 10000):
        pb = PebsSampler(N_PAGES, 0, sample_interval=interval,
                         promote_after=2)
        stream = WORKLOADS["gups"](n_pages=N_PAGES, block=BLOCK,
                                   n_blocks=n_blocks, seed=6)
        det = set()
        n_acc = 0
        for pages in stream:
            det |= set(pb.observe(pages).tolist())
            n_acc += len(pages)
        slowdown = pb.overhead / (n_acc * 200e-9)
        emit(f"fig04c_pebs_interval{interval}", 0.0,
             f"recall={_recall(det, hot):.2f} overhead_frac={slowdown:.3f}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    run()
