"""Work counted from a configuration's published shapes.

Only the bfloat16 target model's *useful* work is counted: what a plain
autoregressive server of the same model must do for the same tokens. A
speculative server does more (γ draft passes, rejected positions), so a
utilization built on this count cannot pass what the device really did.

Per token at context position ``c`` (the token attends to ``c + 1`` keys,
itself included), with ``d`` hidden size, ``H``/``Hkv`` query/KV heads of
size ``hd``, ``F`` the MLP width, ``V`` the vocabulary and ``L`` layers:

    matmul_params = L * (d*H*hd + 2*d*Hkv*hd + H*hd*d + 3*d*F) + d*V
    flops(c)      = 2 * matmul_params + L * 4 * H * hd * (c + 1)

The first term is a multiply-add per weight (Q, K, V, O projections, the
SwiGLU gate, up and down projections, and the LM head; the embedding is a
row lookup and costs none). The second is QK^T and PV: 2 FLOPs per
multiply-add, two products of ``H * hd`` per key. Norms, rotary
embedding, softmax and biases are left out (under 0.1% here).
"""
from __future__ import annotations

import numpy as np


def matmul_params(hf: dict) -> int:
    d = hf["hidden_size"]
    h, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim", d // h)
    per_layer = d * h * hd + 2 * d * hkv * hd + h * hd * d \
        + 3 * d * hf["intermediate_size"]
    return hf["num_hidden_layers"] * per_layer + d * hf["vocab_size"]


def attention_flops_per_key(hf: dict) -> int:
    """FLOPs of one token against one key, over all layers."""
    h = hf["num_attention_heads"]
    hd = hf.get("head_dim", hf["hidden_size"] // h)
    return hf["num_hidden_layers"] * 4 * h * hd


def token_flops(hf: dict, positions) -> float:
    """Useful FLOPs of the tokens at the given context positions."""
    pos = np.asarray(positions, np.float64)
    return float(pos.size * 2 * matmul_params(hf)
                 + attention_flops_per_key(hf) * (pos + 1).sum())


def span_flops(hf: dict, start: int, stop: int) -> float:
    """Useful FLOPs of the tokens at positions ``start .. stop - 1``."""
    return token_flops(hf, np.arange(start, stop))
