"""FLOP counts of bench/work.py against a count by hand."""
import json
from pathlib import Path

import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def hf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["hf_config"]


def test_qwen3_1_7b_by_hand():
    # per layer: q 2048x2048, k and v 2048x1024, o 2048x2048, gate/up/down
    # 3 x 2048x6144 = 50,331,648 weights; x28, + tied head 2048x151936
    assert work.matmul_params(hf("qwen3-1.7b")) == \
        28 * 50_331_648 + 311_164_928 == 1_720_451_072
    # token at position 9 sees 10 keys: 28 layers x 4 x 16 heads x 128
    assert work.token_flops(hf("qwen3-1.7b"), [9]) == \
        2 * 1_720_451_072 + 28 * 4 * 16 * 128 * 10


def test_qwen2_5_3b_by_hand():
    # Qwen/Qwen2.5-3B config.json: 36 layers, 16/2 heads of 128, MLP 11008
    c = {"hidden_size": 2048, "num_attention_heads": 16,
         "num_key_value_heads": 2, "intermediate_size": 11008,
         "num_hidden_layers": 36, "vocab_size": 151936}
    # per layer: q 2048x2048, k and v 2048x256, o 2048x2048, gate/up/down
    # 3 x 2048x11008 = 77,070,336 weights
    assert work.matmul_params(c) == 36 * 77_070_336 + 311_164_928
    assert work.span_flops(c, 0, 3) == \
        3 * 2 * work.matmul_params(c) + 36 * 4 * 16 * 128 * (1 + 2 + 3)


def test_span_is_sum_of_tokens():
    c = hf("qwen3-1.7b")
    assert work.span_flops(c, 100, 140) == sum(
        work.token_flops(c, [p]) for p in range(100, 140))
