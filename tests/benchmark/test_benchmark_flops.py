"""The functions that count a step's required FLOPs, against counts
made by hand from the published shapes."""

import json
import os

import pytest

from benchmark_tiny import spec
from benchmarks import peaks
from benchmarks.models import bert, gpt
from benchmarks.models.common import encoder_flops_per_token, train_flops


def _config(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_block_count_by_hand():
    # H = 2, FFN = 3, one layer, 5 keys a query: 4 projections of
    # 2*2*2, two FFN products of 2*2*3, scores and sum of 2*5*2 each.
    assert encoder_flops_per_token(2, 3, 1, attended=5) == \
        4 * 8 + 2 * 12 + 2 * 20
    assert train_flops(10.0) == 30.0


def test_bert_large_64x128_by_hand():
    h, ffn, layers, vocab, s, b = 1024, 4096, 24, 30522, 128, 64
    per_layer = 8 * h * h + 4 * h * ffn + 4 * s * h
    head = 2 * h * h + 2 * h * vocab
    want = 3 * (layers * per_layer + head) * b * s
    got = bert.flops_per_step(_config("bert-large"), b, s)
    assert got == want
    assert 16.6e12 < got < 16.9e12   # the issue's 16.7 TFLOP a step


def test_gpt2_medium_16x1024_by_hand():
    h, layers, vocab, s, b = 1024, 24, 50257, 1024, 16
    per_layer = 8 * h * h + 4 * h * 4 * h + 4 * (s // 2) * h  # causal half
    want = 3 * (layers * per_layer + 2 * h * vocab) * b * s
    got = gpt.flops_per_step(_config("gpt2-medium"), b, s)
    assert got == want
    assert 36.5e12 < got < 37.9e12   # the issue's 37 TFLOP a step


def test_flops_scale_with_tokens_and_causal_is_cheaper():
    c = _config("gpt2-medium")
    assert gpt.flops_per_step(c, 8, 512) * 2 < gpt.flops_per_step(c, 16, 512)\
        * 1.0001
    b = dict(_config("bert-large"), vocab_size=c["vocab_size"])
    # Same widths and depth: the full square and BERT's head transform
    # make the encoder's step the dearer one.
    assert bert.flops_per_step(b, 4, 1024) > gpt.flops_per_step(c, 4, 1024)


def test_peak_table_knows_the_v5e_and_refuses_the_unknown():
    peak = peaks.peak_of("TPU v5 lite")
    assert peak.bf16_flops_per_s == 197e12
    assert peak.hbm_bytes_per_s == 819e9
    with pytest.raises(ValueError, match="no peak listed"):
        peaks.peak_of("cpu")
