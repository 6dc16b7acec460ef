"""The check that decides ``correct`` catches a broken timed path: each
fault a serving cell can have is planted under a harness run (on the CPU,
at a tiny size, the chip check skipped) and ``correct`` comes out false."""
import numpy as np
import pytest

import bench_cells
from repro.serve.engine import ServeEngine


def _alter_token(monkeypatch):
    """The token a lane is served is not the one its logits put first."""
    orig = ServeEngine.advance_lanes

    def advance(self, tokens, active, segments):
        out = np.array(orig(self, tokens, active, segments))
        top = out[0].argmax()
        out[0, (top + 1) % out.shape[1]] = out[0, top] + 1.0
        return out
    monkeypatch.setattr(ServeEngine, "advance_lanes", advance)


def _state_unchanged(monkeypatch):
    """The decode step returns the cache it was given."""
    orig = ServeEngine._decode_paged_fn

    def step(self, params, cache, token, tiered, active):
        out = orig(self, params, cache, token, tiered, active)
        return (out[0], cache) + tuple(out[2:])
    monkeypatch.setattr(ServeEngine, "_decode_paged_fn", step)


def _half_batch(monkeypatch):
    """Half of the lanes are left out: they are served lane 0's logits."""
    orig = ServeEngine.advance_lanes

    def advance(self, tokens, active, segments):
        out = np.array(orig(self, tokens, active, segments))
        half = out.shape[0] // 2
        out[half:] = out[0]
        return out
    monkeypatch.setattr(ServeEngine, "advance_lanes", advance)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_fault_makes_the_run_incorrect(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    out = bench_cells.drive(bench_cells.tiny_root(tmp_path), seed=9,
                            seconds=1.0)
    gap = out["compared"]["max_logit_gap"]
    assert out["correct"] is False, gap
    assert gap["value"] > gap["limit"]
