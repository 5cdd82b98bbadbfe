"""The benchmark's yardstick: the work the served requests need."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import costs, spec  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(params=["qwen3-8b-l8", "phi3-mini-3.8b"])
def conf(request):
    return spec.load_json(os.path.join(spec.BENCH, "configs",
                                       request.param + ".json"))


def test_needed_work_ignores_the_arena(conf):
    """Rows the program steps beyond the served ones, and the arena's
    max_len, are not work the requests need."""
    pos = np.array([3, 100, 511, 40])
    small = dict(conf, serving={"capacity": 4, "max_len": 512,
                                "max_wait_s": 0.01})
    big = dict(conf, serving={"capacity": 512, "max_len": 4096,
                              "max_wait_s": 0.01})
    for c in (small, big):
        assert np.array_equal(costs.row_flops(c, pos),
                              costs.row_flops(conf, pos))
        assert np.array_equal(costs.row_bytes(c, pos),
                              costs.row_bytes(conf, pos))
        assert costs.step_least_seconds(c, [4], pos, PEAKS) == \
            costs.step_least_seconds(conf, [4], pos, PEAKS)


def test_needed_work_grows_with_actual_length(conf):
    short, long_ = np.array([10, 10]), np.array([10, 900])
    assert (costs.row_flops(conf, long_) > costs.row_flops(conf, short)).any()
    assert costs.row_bytes(conf, long_).sum() > \
        costs.row_bytes(conf, short).sum()
    t_s, _ = costs.step_least_seconds(conf, [2], short, PEAKS)
    t_l, _ = costs.step_least_seconds(conf, [2], long_, PEAKS)
    assert t_l > t_s
    # one more row per position of cache: exactly one position's KV more
    kv = costs.kv_bytes_per_position(conf)
    assert costs.row_bytes(conf, 11) - costs.row_bytes(conf, 10) == kv


def test_counts_by_hand():
    """A tiny configuration, counted by hand."""
    c = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 3,
         "cut_layer": 1, "num_attention_heads": 2, "num_key_value_heads": 1,
         "head_dim": 4, "vocab_size": 10, "qk_norm": True,
         "torch_dtype": "bfloat16"}
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16      # 576
    assert costs.layer_params(c) == per_layer
    assert costs.top_weight_bytes(c) == 2 * (
        2 * (per_layer + 16 + 8) + 8 + 8 * 10)
    assert costs.kv_bytes_per_position(c) == 2 * 2 * 1 * 4 * 2
    # position 5 attends over 6 positions in each of the 2 top layers
    assert costs.row_flops(c, 5) == 2 * (2 * per_layer + 80) + \
        4 * 2 * 4 * 6 * 2
    assert costs.row_bytes(c, 5) == 32 * 6 + 32 + 16 + 4


def test_flushes_pay_the_weights_once_each(conf):
    pos = np.arange(64)
    one, bound = costs.step_least_seconds(conf, [64], pos, PEAKS)
    two, _ = costs.step_least_seconds(conf, [32, 32], pos, PEAKS)
    assert bound == "bytes"
    assert two - one == pytest.approx(
        costs.top_weight_bytes(conf) / PEAKS["hbm_bytes_per_s"])
