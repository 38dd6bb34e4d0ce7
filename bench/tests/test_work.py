"""FLOP counts of each model type's work count against a count by hand."""
import json
from pathlib import Path

import harness

BENCH = Path(__file__).resolve().parents[1]
qwen3 = harness.load_module(BENCH / "arch" / "qwen3.py")


def conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_qwen3_1_7b_by_hand():
    # per layer: q 2048x2048, k and v 2048x1024, o 2048x2048, gate/up/down
    # 3 x 2048x6144 = 50,331,648 weights; x28, + tied head 2048x151936
    assert qwen3.matmul_params(conf("qwen3-1.7b")) == \
        28 * 50_331_648 + 311_164_928 == 1_720_451_072
    # token at position 9 sees 10 keys: 28 layers x 4 x 16 heads x 128
    assert qwen3.token_flops(conf("qwen3-1.7b"), [9]) == \
        2 * 1_720_451_072 + 28 * 4 * 16 * 128 * 10


def test_qwen2_5_3b_by_hand():
    # Qwen/Qwen2.5-3B config.json: 36 layers, 16/2 heads of 128, MLP 11008
    c = {"hf_config": {"hidden_size": 2048, "num_attention_heads": 16,
                       "num_key_value_heads": 2, "intermediate_size": 11008,
                       "num_hidden_layers": 36, "vocab_size": 151936}}
    # per layer: q 2048x2048, k and v 2048x256, o 2048x2048, gate/up/down
    # 3 x 2048x11008 = 77,070,336 weights
    assert qwen3.matmul_params(c) == 36 * 77_070_336 + 311_164_928
    assert qwen3.span_flops(c, 0, 3) == \
        3 * 2 * qwen3.matmul_params(c) + 36 * 4 * 16 * 128 * (1 + 2 + 3)


def test_span_is_sum_of_tokens():
    c = conf("qwen3-1.7b")
    assert qwen3.span_flops(c, 100, 140) == sum(
        qwen3.token_flops(c, [p]) for p in range(100, 140))
