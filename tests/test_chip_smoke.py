"""chip_smoke.py's phase functions at smoke size on the CPU backend.

The script itself refuses to run without a TPU; its phases take the model
config and sizes as arguments, so the same serving, reference and training
code runs here on qwen1.5-4b's smoke config, with the Pallas kernel in
interpret mode and the slow stores in (CPU) pinned host memory.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs.registry import get_smoke_config
from repro.dist import host_offload as ho

ROOT = Path(__file__).resolve().parents[1]

SIZES = dict(lanes=2, page_t=4, hot_slots=6, max_seq=64, kv_segments=3,
             kv_quota=4, embed_rows_per_page=8, embed_hot_slots=4,
             embed_quota=4, migration_interval=4, prefill_chunk=8,
             prompt_lens=(12, 10, 17), max_new=4)


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(cs):
    cfg = get_smoke_config(cs.ARCH)
    sizes = cs.ServeSizes(**SIZES)
    params = cs.random_params(cfg, 0)
    prompts = cs.make_prompts(cfg.vocab, sizes.prompt_lens, 0)
    arms = {a: cs.serve(cfg, params, sizes, prompts, a) for a in (False, True)}
    return cfg, params, arms


def test_serve_phase_sync_async_parity(cs, served):
    """Both data planes emit identical tokens and move equal bytes through
    pinned-host slow stores, and both tiers serve reads."""
    _, _, arms = served
    moved = cs.check_serving(arms[False], arms[True])
    assert set(moved) == {"embeddings", "kv"} and sum(moved.values()) > 0
    assert arms[False]["n_tokens"] == 3 * SIZES["max_new"]
    for res in arms.values():
        assert set(sum(res["memory_kinds"].values(), [])) == {ho.SLOW_KIND}
        assert res["kernel_in_decode"] is False      # interpreted on CPU


def test_serve_phase_matches_float32_reference(cs, served):
    """The first decode step's logits agree with the float32 dense forward
    within the script's stated bound, and pick the same token."""
    cfg, params, arms = served
    res = arms[False]
    ref = cs.reference_logits(cfg, params, res["watched_tokens"])
    err = cs.logit_error(res["watched_logits"], ref)
    assert err["rel_l2"] <= cs.LOGIT_REL_L2_BOUND
    assert err["top1_agree"]


def test_check_serving_rejects_token_mismatch(cs, served):
    _, _, arms = served
    bad = dict(arms[True], tokens=[[0]] + arms[True]["tokens"][1:])
    with pytest.raises(RuntimeError, match="different tokens"):
        cs.check_serving(arms[False], bad)


def test_train_phase_runs_with_offloaded_zero1(cs):
    """The four-chip phase's two train steps at smoke size on one device:
    finite, matching losses; the ZeRO-1 trees parked in host memory and
    passing the script's own sharding check."""
    cfg = dataclasses.replace(get_smoke_config(cs.ARCH), n_layers=2)
    # lr 1e-4: at smoke widths lr 1e-5 barely moves the loss
    sizes = cs.TrainSizes(n_layers=2, global_batch=4, seq_len=16,
                          microbatches=2, steps=2, lr=1e-4)
    dev = jax.devices()[:1]
    many, many_g, state = cs.train_run(
        cfg, sizes, cs.sharded_config(sizes.lr, sizes.microbatches), dev, 0)
    one, one_g, _ = cs.train_run(
        cfg, sizes, cs.reference_config(sizes.lr, sizes.microbatches), dev, 0)
    assert len(many) == len(many_g) == 2
    cs.check_losses(many, one)
    cs.check_gnorm(many_g, one_g)
    shards = cs.zero1_shards(state["opt"])
    assert set(shards) == {"m", "v"}
    # XLA:CPU returns a program's outputs in device memory (DESIGN.md §7):
    # the state is parked in host memory between steps only on a TPU
    cs.check_zero1(shards, [dev[0].id], kind="device")


def test_check_losses_rejects_a_diverged_run(cs):
    """A first loss off by more than reduction order, or a later one off
    by a large share of the reference's own movement, fails the check."""
    one = [11.4, 11.3, 11.2]
    assert cs.check_losses([11.4, 11.299, 11.202], one)[0] == 0.0
    with pytest.raises(RuntimeError, match="disagree"):
        cs.check_losses([11.41, 11.3, 11.2], one)
    with pytest.raises(RuntimeError, match="disagree"):
        cs.check_losses([11.4, 11.35, 11.2], one)
    with pytest.raises(RuntimeError, match="disagree"):
        cs.check_losses([11.4, float("nan"), 11.2], one)


def test_train_phase_gradient_scale_on_four_devices():
    """On a 4-device data mesh the deferred all-reduce averages the
    per-device mean gradients: the first step's gradient norm equals the
    partitioner's plain reduce (fp32 manual reduce) and the one-device
    reference (the script's int8+EF phase, within its bound).  A reduce
    that summed them would give 4x the norm, which the losses cannot show."""
    code = f"""
        import dataclasses, importlib.util, sys
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = cs
        spec.loader.exec_module(cs)
        import jax
        from repro.configs.registry import get_smoke_config
        cfg = dataclasses.replace(get_smoke_config(cs.ARCH), n_layers=2)
        sizes = cs.TrainSizes(n_layers=2, global_batch=8, seq_len=16,
                              microbatches=2, steps=1, lr=1e-4)
        devs = jax.devices()
        assert len(devs) == 4
        ref = cs.reference_config(sizes.lr, sizes.microbatches)
        _, one, _ = cs.train_run(cfg, sizes, ref, devs[:1], 0)
        _, many, _ = cs.train_run(
            cfg, sizes, cs.sharded_config(sizes.lr, sizes.microbatches),
            devs, 0)
        _, plain, _ = cs.train_run(cfg, sizes, ref, devs, 0)
        _, local, _ = cs.train_run(
            cfg, sizes, dataclasses.replace(ref, local_grads=True), devs, 0)
        print("GAPS", cs.check_gnorm(many, one),
              abs(local[0] - plain[0]) / plain[0])
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    int8_gap, fp32_gap = map(float, r.stdout.split("GAPS")[1].split())
    assert int8_gap <= 1e-2
    assert fp32_gap <= 1e-4


def test_check_gnorm_rejects_a_summed_reduce(cs):
    assert cs.check_gnorm([2.8801], [2.8793]) < 1e-3
    with pytest.raises(RuntimeError, match="gradient norm"):
        cs.check_gnorm([4 * 2.8793], [2.8793])


def test_check_zero1_rejects_state_held_whole(cs):
    """A vector held whole on each device (replicated, not sharded) or
    missing from a device fails the script's sharding check."""
    whole = {"m": {"memory_kinds": [ho.SLOW_KIND], "bytes": 400,
                   "bytes_per_device": {0: 400, 1: 400, 2: 400, 3: 400}}}
    with pytest.raises(RuntimeError, match="not sharded"):
        cs.check_zero1(whole, [0, 1, 2, 3])
    partial = {"m": {"memory_kinds": [ho.SLOW_KIND], "bytes": 400,
                     "bytes_per_device": {0: 400}}}
    with pytest.raises(RuntimeError, match="not spread"):
        cs.check_zero1(partial, [0, 1, 2, 3])


def test_script_refuses_to_run_without_tpu():
    """On the CPU backend the script exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr
