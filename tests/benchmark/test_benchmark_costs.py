"""The operation and byte functions against numbers worked out by hand."""

import json
import os

import pytest

from benchmarks import costs, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_base_layer_forward_flops_at_t128():
    # 4 projections of 768x768 and two of 768x3072, two operations a
    # multiply-add: 2*(4*589824 + 2*2359296) = 14155776; attention
    # q.k^T and p.v over 128 keys: 2*2*128*768 = 393216
    assert costs.bert_layer_forward_flops_per_token(
        config("bert_base"), 128) == 14155776 + 393216


def test_bert_base_train_flops_per_token_at_t128():
    cfg = config("bert_base")
    encoder = 12 * 128 * 14548992                    # 22347251712
    mlm = 19 * (2 * 768 * 768 + 2 * 768 * 30522)     # 913167360
    nsp = 2 * 768 * 768 + 2 * 768 * 2                # 1182720
    assert costs.bert_forward_flops_per_sequence(cfg, 128, 19) \
        == encoder + mlm + nsp == 23261601792
    assert costs.bert_train_flops_per_token(cfg, 128, 19) \
        == pytest.approx(3 * 23261601792 / 128)      # 545.2 MFLOP a token


def test_bert_base_train_flops_grow_with_the_sequence():
    cfg = config("bert_base")
    assert costs.bert_train_flops_per_token(cfg, 512, 76) \
        == pytest.approx(587640330.0)


def test_bert_base_param_count():
    # 12 layers of 7087872, embeddings 23837184 + LayerNorm 1536, heads:
    # pooler 590592, transform 590592 + LayerNorm 1536, vocabulary bias
    # 30522, next-sentence 1538
    assert costs.bert_param_count(config("bert_base")) == 110106428


def test_gpt2_xl_param_count_is_the_published_1_5_billion():
    assert costs.gpt_param_count(config("gpt2_xl")) == 1557611200


@pytest.mark.parametrize("backward,products,tensors", [(False, 2, 4),
                                                       (True, 4, 8)])
def test_flash_attention_costs(backward, products, tensors):
    b, h, t, d = 32, 12, 512, 64
    assert costs.flash_attention_flops(b, h, t, d, backward) \
        == products * 2 * b * h * t * t * d
    assert costs.flash_attention_bytes(b, h, t, d, backward) \
        == tensors * b * h * t * d * 2


def test_fused_adamw_bytes():
    # weight, gradient, two moments read; weight, two moments written
    assert costs.fused_adamw_bytes(1000) == 7 * 4000


def test_roofline_takes_the_longer_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert costs.roofline_seconds(197e12, 1.0, v5e) == pytest.approx(1.0)
    assert costs.roofline_seconds(1.0, 819e9, v5e) == pytest.approx(1.0)


def test_gpt2_xl_decode_step_is_bound_by_reading_the_weights():
    cfg, v5e = config("gpt2_xl"), peaks.peaks_for("TPU v5 lite")
    floor = costs.gpt_decode_step_floor_seconds(cfg, 4, 320, v5e)
    weights = 1557611200 * 4
    kv = 2 * 48 * 320 * 1600 * 4
    assert floor == pytest.approx((weights + kv) / 819e9)     # 7.7 ms
    assert floor > 2 * 1557611200 * 4 / 197e12


def test_a_device_that_is_not_in_the_table_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
