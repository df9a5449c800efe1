"""Required operations and bytes of the GPT-2 decoder, from shapes alone.

The yardstick every roofline share and MFU of a ``"counts": "gpt2"``
configuration divides by.  Convention: one multiply-add is 2 FLOPs; only
the operations the algorithm *requires* are counted (no recomputation
under remat, no padding), and causal attention is counted causally — half
of the full S x S score and value products — because that is all a causal
model has to compute.

The shapes come from the configuration file's top level (``n_layer``,
``n_embd``, ``n_head``, ``vocab_size``, ``n_inner``).

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token(config, seq_len)``, ``decode_iter_bytes(config,
live_kv_tokens, weight_dtype_bytes)`` and ``step_kernel(config, name)``
for each kernel a layer metric's ``args.required`` names.
"""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    fused qkv, attention projection, both MLP matrices in every layer, and
    the tied output head (the embedding lookup itself is a gather)."""
    d, inner = model["n_embd"], model["n_inner"]
    per_layer = d * 3 * d + d * d + 2 * d * inner
    return model["n_layer"] * per_layer + model["vocab_size"] * d


def attention_flops_per_token(model: dict, seq_len: int,
                              causal: bool = True) -> float:
    """Score and value products of self-attention, forward + backward, per
    token of a ``seq_len`` sequence: 12·L·d·S counted in full (forward
    4·d·S per layer, backward twice that), half of it counted causally."""
    full = 12.0 * model["n_layer"] * model["n_embd"] * seq_len
    return full / 2 if causal else full


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one training token requires:
    6 x matmul parameters + causal attention."""
    return 6.0 * matmul_params(model) + attention_flops_per_token(
        model, seq_len, causal=True)


def flash_flops(model: dict, batch: int, seq_len: int) -> dict:
    """Required FLOPs of all layers' attention kernels for one training
    step on ``batch`` sequences, causal: forward 4·d·S²/2 per sequence and
    layer, backward 2x that (dq, dk, dv and the recomputed scores are the
    kernel's business; the requirement is 2x forward)."""
    fwd = model["n_layer"] * batch * 4.0 * model["n_embd"] * seq_len ** 2 / 2
    return {"fwd": fwd, "bwd": 2.0 * fwd}


def flash_bytes(model: dict, batch: int, seq_len: int,
                dtype_bytes: int = 2) -> dict:
    """HBM bytes the attention kernels must move per step: forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes dq, dk,
    dv (each ``batch x seq x d`` in the compute dtype)."""
    t = model["n_layer"] * batch * seq_len * model["n_embd"] * dtype_bytes
    return {"fwd": 4.0 * t, "bwd": 8.0 * t}


def xent_flops(model: dict, tokens: int) -> float:
    """Required FLOPs of the fused loss head per step: logits forward,
    and in the backward the gradient to the hidden states and to the
    table: 3 products of ``tokens x d x V``, 2 FLOPs per multiply-add."""
    return 6.0 * tokens * model["n_embd"] * model["vocab_size"]


def xent_bytes(model: dict, tokens: int, table_bytes: int = 4,
               act_bytes: int = 2) -> float:
    """HBM bytes the loss head must move per step if logits never leave
    fast memory: hidden states read (forward and both backward kernels)
    and their gradient written, the table read three times and its
    gradient written once."""
    d, v = model["n_embd"], model["vocab_size"]
    return 4.0 * tokens * d * act_bytes + 4.0 * v * d * table_bytes


def weight_bytes(model: dict, dtype_bytes: int) -> float:
    """Bytes of the weights a forward pass reads once (matmul parameters;
    LayerNorm vectors are noise beside them)."""
    return float(matmul_params(model)) * dtype_bytes


def kv_bytes_per_token(model: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token over all layers."""
    return 2 * model["n_layer"] * model["n_embd"] * dtype_bytes


def decode_iter_bytes(model: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2) -> float:
    """Bytes one decode iteration must read: the weights once (whatever the
    batch) plus the K/V of every live token of every running sequence."""
    return weight_bytes(model, weight_dtype_bytes) + (
        live_kv_tokens * kv_bytes_per_token(model, kv_dtype_bytes))


def step_kernel(config: dict, name: str) -> dict:
    """``{"flops", "bytes"}`` the kernel family ``name`` requires in one
    training step on one chip (``per_chip_batch`` sequences of
    ``seq_len``): what reader ``trace_roofline`` divides the traced kernel
    time into."""
    batch, seq = config["per_chip_batch"], config["seq_len"]
    if name == "flash":
        f, b = flash_flops(config, batch, seq), flash_bytes(config, batch, seq)
        return {"flops": f["fwd"] + f["bwd"], "bytes": b["fwd"] + b["bwd"]}
    if name == "xent":
        tokens = batch * (seq - 1)
        return {"flops": xent_flops(config, tokens),
                "bytes": xent_bytes(config, tokens)}
    raise KeyError(f"counts/gpt2.py has no kernel {name!r}")
