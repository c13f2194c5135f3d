"""FLOP arithmetic and peaks against the figures worked by hand in
PERF.md (section 3, "worker step")."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

from benchmark.harness import flops, peaks  # noqa: E402


def sizes(config):
    path = os.path.join(ROOT, "benchmark", "configs", config, "config.json")
    with open(path) as f:
        return json.load(f)


def test_resnet50_at_224_is_4_089_gmacs_forward():
    # the known count for the stride-on-3x3 arrangement; x2 FLOPs, x3 training
    assert flops.flops_per_sample(sizes("resnet50-224")) == 24_535_105_536
    assert 24_535_105_536 == 3 * 2 * 4_089_184_256


def test_dense_160m_at_2048_tokens():
    # per token: 12 x (4 x 768^2 + 2 x 768 x 3072) + 768 x 50304
    #   = 84_934_656 + 38_633_472 = 123_568_128 weight MACs,
    # causal attention 12 x 2 x 768 x 2049 / 2 = 18_883_584 MACs,
    # x 6 = 854_710_272 FLOPs a token, x 2048 tokens a sample
    weights = 12 * (4 * 768**2 + 2 * 768 * 3072) + 768 * 50304
    assert weights == 123_568_128
    per_token = 6 * (weights + 12 * 2 * 768 * 2049 / 2)
    assert per_token == 854_710_272
    assert flops.flops_per_sample(sizes("lm-dense-160m")) == pytest.approx(
        per_token * 2048, rel=1e-12
    )


def test_a_formula_the_table_lacks_is_looked_for_beside_the_config(tmp_path):
    (tmp_path / "flops.py").write_text(
        "def flops_per_sample(sizes):\n    return 7 * sizes['n']\n"
    )
    own = {"flops": {"formula": "mine"}, "n": 3}
    assert flops.flops_per_sample(own, str(tmp_path)) == 21
    with pytest.raises(KeyError):
        flops.flops_per_sample(own, str(tmp_path / "nowhere"))


def test_peaks_know_the_v5e_and_refuse_an_unknown_device():
    assert peaks.peak("TPU v5 lite") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("TPU v9 imaginary")
