"""Operations and bytes of the served work, against hand counts at the
published widths of qwen1.5-4b (a cell's configuration) and of
stablelm-2-1_6b (head_dim 64, LayerNorm; written out here), counted by the
dense decoder's module (bench/arch/qwen2.py); and the per-layer readers
that use them, against the values the counts had before they moved into
that module."""
from types import SimpleNamespace

import numpy as np
import pytest

import bench_cells
from bench import model, work
from bench.arch import qwen2
from bench.spec import load_cell, reader


# stablelm-2-1_6b's published widths
_STABLELM = {"name": "stablelm-1.6b", "model_type": "stablelm",
             "hidden_size": 2048, "intermediate_size": 5632,
             "num_attention_heads": 32, "num_key_value_heads": 32,
             "num_hidden_layers": 24, "vocab_size": 100352,
             "layer_norm_eps": 1e-5, "rope_theta": 10000,
             "use_qkv_bias": True}


def _f(name):
    if name == "stablelm-1.6b":
        return qwen2.fields(_STABLELM)
    return qwen2.fields(load_cell(name).conf)


def test_matmul_params_by_hand():
    # qwen1.5-4b: per layer 2560*(20+2*20)*128 + 2560*2560 + 3*2560*6912
    # = 19,660,800 + 6,553,600 + 53,084,160; 40 layers; head 151936*2560
    assert qwen2.matmul_params(_f("qwen1.5-4b.chat")) == \
        40 * 79_298_560 + 388_956_160
    # stablelm-1.6b: 2048*96*64 + 2048*2048 + 3*2048*5632; 24 layers;
    # head 100352*2048
    assert qwen2.matmul_params(_f("stablelm-1.6b")) == \
        24 * 51_380_224 + 205_520_896


def test_token_flops_by_hand():
    f = _f("qwen1.5-4b.longchat")
    # 2 * 3,560,898,560 for the weights, 4*20*128*40 per attended key
    assert qwen2.token_flops(f, [0, 10]).tolist() == [
        7_121_797_120.0, 7_121_797_120.0 + 10 * 409_600]


def test_paged_attn_call_by_hand():
    f = _f("qwen1.5-4b.chat")
    flops, nbytes = qwen2.paged_attn_call(f, [100, 512])
    # QK^T and PV: 4 * 20 heads * 128 * 612 keys
    assert flops == 4 * 20 * 128 * 612
    # bf16 K and V of 612 keys x 20 kv heads x 128, plus per lane the f32
    # query (20*128) and output (20*128 numerator, 20 max, 20 denominator)
    assert nbytes == 2 * 2 * 20 * 128 * 612 + 2 * (4 * 2560 + 4 * 2600)
    f = _f("stablelm-1.6b")
    flops, nbytes = qwen2.paged_attn_call(f, [64])
    assert flops == 4 * 32 * 64 * 64
    assert nbytes == 2 * 2 * 32 * 64 * 64 + 4 * 2048 + 4 * (2048 + 64)


@pytest.mark.parametrize("pos,keys", [(0, 1), (63, 64), (510, 511),
                                      (511, 448), (512, 449), (1000, 489)])
def test_attended_keys_follow_the_ring(pos, keys):
    # 8 slots of 64: a page that fills gives up the oldest slot at once
    assert int(work.attended(pos, 64, 8)) == keys


def test_window_mask_and_attended_agree():
    mask = model.window_mask(700, 64, 8)
    assert np.array_equal(mask.sum(axis=1), work.attended(np.arange(700),
                                                          64, 8))
    assert np.array_equal(mask[:511], np.tril(np.ones((700, 700), bool))[:511])


def test_least_time_is_memory_bound_on_v5e():
    f = _f("qwen1.5-4b.longchat")
    peaks = work.device_peaks("TPU v5 lite")
    least, bound = work.paged_attn_least_s(qwen2, f, [[100, 600], []], peaks,
                                           64, 8)
    _, nbytes = qwen2.paged_attn_call(f, work.attended([100, 600], 64, 8))
    assert bound == "memory"
    assert least == pytest.approx(40 * nbytes / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.device_peaks("TPU v9")


# Decode bodies (the positions of the active lanes of each) and the values
# their work had at qwen1.5-4b's widths before the counts moved into the
# architecture module, computed by the same arithmetic.
BODIES = [[0, 1, 2, 3], [100, 600], [], [511, 512, 1000, 2047], [63]]
LEAST_S = 0.0012523916971916974


def test_least_time_keeps_its_value_on_fixed_bodies():
    f = _f("qwen1.5-4b.chat")
    least, bound = work.paged_attn_least_s(
        qwen2, f, BODIES, work.device_peaks("TPU v5 lite"), 64, 8)
    assert (least, bound) == (LEAST_S, "memory")


@pytest.mark.parametrize("metric,value", [
    ("mfu", 0.001964996793859106),
    ("paged_attn_roofline", 62.61958485958487)])
def test_readers_keep_their_values_on_fixed_bodies(metric, value):
    # a 20.5 s window holding BODIES, and a trace whose window holds 2 ms
    # of paged_attn among other ops
    ctx = SimpleNamespace(
        arch=qwen2, f=_f("qwen1.5-4b.chat"), t0=10.0, t1=30.5,
        geo={"page_t": 64, "ring_pages": 8},
        peaks=work.device_peaks("TPU v5 lite"),
        bodies=[(10.0 + i, b) for i, b in enumerate(BODIES)],
        trace={"ops": [["paged_attn.6", 0, 2_000_000],
                       ["fusion.1", 0, 5_000_000]], "modules": [],
               "spans": [["bench.window", 0, 10**9]]})
    got = reader(SimpleNamespace(root=bench_cells.REPO), metric).read(ctx)
    assert got == value
