"""Required operations and bytes of the rehearsal's grouped-query decoder;
found only under this rehearsal root.  K and V are ``n_kv_head`` heads
wide, so the fused qkv matrix and a token's K/V are narrower than GPT-2's.
"""

from __future__ import annotations


def _kv_width(config: dict) -> int:
    return config["n_embd"] // config["n_head"] * config["n_kv_head"]


def matmul_params(config: dict) -> int:
    d, inner = config["n_embd"], config["n_inner"]
    per_layer = d * (d + 2 * _kv_width(config)) + d * d + 2 * d * inner
    return config["n_layer"] * per_layer + config["vocab_size"] * d


def train_flops_per_token(config: dict, seq_len: int) -> float:
    causal_attention = 6.0 * config["n_layer"] * config["n_embd"] * seq_len
    return 6.0 * matmul_params(config) + causal_attention


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int,
                      kv_dtype_bytes: int = 2) -> float:
    kv_token = 2 * config["n_layer"] * _kv_width(config) * kv_dtype_bytes
    return (float(matmul_params(config)) * weight_dtype_bytes
            + live_kv_tokens * kv_token)


def step_kernel(config: dict, name: str) -> dict:
    raise KeyError(f"counts/gqa_decoder.py has no kernel {name!r}")
