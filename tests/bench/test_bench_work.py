"""Operations and bytes of the served work, against hand counts at the
published widths of qwen1.5-4b (a cell's configuration) and of
stablelm-2-1_6b (head_dim 64, LayerNorm; written out here)."""
import numpy as np
import pytest

import bench_cells  # noqa: F401
from bench import model, work
from bench.spec import load_cell


# stablelm-2-1_6b's published widths
_STABLELM = {"name": "stablelm-1.6b", "model_type": "stablelm",
             "hidden_size": 2048, "intermediate_size": 5632,
             "num_attention_heads": 32, "num_key_value_heads": 32,
             "num_hidden_layers": 24, "vocab_size": 100352,
             "layer_norm_eps": 1e-5, "rope_theta": 10000,
             "use_qkv_bias": True}


def _f(name):
    if name == "stablelm-1.6b":
        return model.arch_fields(_STABLELM)
    return model.arch_fields(load_cell(name).conf)


def test_matmul_params_by_hand():
    # qwen1.5-4b: per layer 2560*(20+2*20)*128 + 2560*2560 + 3*2560*6912
    # = 19,660,800 + 6,553,600 + 53,084,160; 40 layers; head 151936*2560
    assert work.matmul_params(_f("qwen1.5-4b.chat")) == \
        40 * 79_298_560 + 388_956_160
    # stablelm-1.6b: 2048*96*64 + 2048*2048 + 3*2048*5632; 24 layers;
    # head 100352*2048
    assert work.matmul_params(_f("stablelm-1.6b")) == \
        24 * 51_380_224 + 205_520_896


def test_token_flops_by_hand():
    f = _f("qwen1.5-4b.longchat")
    # 2 * 3,560,898,560 for the weights, 4*20*128*40 per attended key
    assert work.token_flops(f, [0, 10]).tolist() == [
        7_121_797_120.0, 7_121_797_120.0 + 10 * 409_600]


def test_paged_attn_call_by_hand():
    f = _f("qwen1.5-4b.chat")
    flops, nbytes = work.paged_attn_call(f, [100, 512])
    # QK^T and PV: 4 * 20 heads * 128 * 612 keys
    assert flops == 4 * 20 * 128 * 612
    # bf16 K and V of 612 keys x 20 kv heads x 128, plus per lane the f32
    # query (20*128) and output (20*128 numerator, 20 max, 20 denominator)
    assert nbytes == 2 * 2 * 20 * 128 * 612 + 2 * (4 * 2560 + 4 * 2600)
    f = _f("stablelm-1.6b")
    flops, nbytes = work.paged_attn_call(f, [64])
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
    least, bound = work.paged_attn_least_s(f, [[100, 600], []], peaks, 64, 8)
    _, nbytes = work.paged_attn_call(f, work.attended([100, 600], 64, 8))
    assert bound == "memory"
    assert least == pytest.approx(40 * nbytes / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.device_peaks("TPU v9")
