"""Operations and bytes from shapes, against values worked by hand."""

import numpy as np
import pytest

from benchmarks.flops import bert, resnet
from benchmarks.harness import manifest
from benchmarks.tests.tiny import ROOT

BERT = manifest.Manifest(ROOT).cell("bert_s512").config["published"]
RESNET = manifest.Manifest(ROOT).cell("resnet50_i224").config["published"]


def test_bert_base_dense_flops_per_token():
    # per layer: QKV 2*768*2304 + out 2*768*768 + MLP 4*768*3072 = 14_155_776
    # head: 2*768*768 + 2*768*30522 = 48_061_440
    assert bert.dense_flops_per_token(BERT) == 12 * 14_155_776 + 48_061_440
    assert bert.dense_flops_per_token(BERT) == 217_930_752


def test_one_attention_call_at_8192():
    # QK^T and PV: 2 * 2 * S^2 * (heads * head_dim) = 4 * 8192^2 * 768
    one_layer = dict(BERT, num_hidden_layers=1)
    assert bert.attention_flops_forward(np.array([8192]), one_layer) \
        == 4 * 8192 ** 2 * 768 == 206_158_430_208
    batch = {"input_ids": np.zeros((2, 8192), np.int32)}
    work = bert.attention_kernel_work(batch, one_layer, rows_per_chip=2)
    assert work["forward_flops"] == 2 * 206_158_430_208
    assert work["backward_flops"] == 2.5 * work["forward_flops"]
    # q, k, v, o in bf16 + the log-sum-exp in f32
    assert work["forward_bytes"] == 4 * 2 * 8192 * 768 * 2 + 2 * 12 * 8192 * 4
    # attention is 58% of the model's operations at 8192
    full = bert.train_flops(batch, BERT) / 3
    share = bert.attention_flops_forward(np.array([8192, 8192]), BERT) / full
    assert share == pytest.approx(0.58, abs=0.005)


def test_packed_rows_count_block_diagonal_attention_and_no_padding():
    seg = np.zeros((1, 512), np.int32)
    seg[0, :100], seg[0, 100:300] = 1, 2          # 212 padding positions
    batch = {"input_ids": seg, "segment_ids": seg}
    assert sorted(bert.document_lengths(batch)) == [100, 200]
    expect = 300 * 217_930_752 + 4 * 768 * 12 * (100 ** 2 + 200 ** 2)
    assert bert.train_flops(batch, BERT) == 3 * expect


def test_resnet50_forward_is_4_1_gmacs_at_224():
    macs = resnet.forward_macs(224, RESNET)
    assert macs == 4_089_184_256
    assert macs == pytest.approx(4.1e9, rel=0.01)
    batch = {"image": np.zeros((128, 224, 224, 3), np.int8),
             "label": np.zeros(128, np.int32)}
    assert resnet.train_flops(batch, RESNET) == 3 * 2 * macs * 128
