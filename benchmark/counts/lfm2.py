"""Required operations and bytes of the LiquidAI LFM2-MoE decoder (gated
short-convolution layers, a grouped-query attention layer every fourth, 64
routed experts in every layer past the leading dense ones), from shapes alone.

The yardstick every roofline share of a ``"counts": "lfm2"`` configuration
divides by.  Convention as in ``counts/gpt2.py``: one multiply-add is 2
FLOPs, only what the algorithm *requires* is counted.  Shapes come from the
configuration file's top level (the published keys: ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``moe_intermediate_size``, ``num_experts``, ``num_experts_per_tok``,
``layer_types``, ``num_dense_layers``, ``conv_L_cache``, ``vocab_size``;
``head_dim`` is ``hidden_size / num_attention_heads`` where the file has none).

What one decode iteration must move: the weights outside the routed experts
once whatever the batch (the tied embedding once: the head reads it, the
lookup is a gather); each expert *that some token of the batch is routed to*
once (an expert nobody chose is not needed: at 96 slots top 4 of 64 uniform
routing leaves 0.2 of 64 unhit a layer, a random router more); each live
sequence's convolution tails read and written; the attention layers' K and V
of every live token.  Which experts are hit depends on the weights; the
requirement uses the expectation under uniform routing, ``experts x (1 - (1
- k / experts) ** tokens)``, unless the step log's counters are handed in.

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for reader
``trace_decode_kernel``, which knows every live sequence's length and the
step log's routing counters.
"""

from __future__ import annotations

DTYPE_BYTES = 2


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def conv_layers(c: dict) -> int:
    return sum(kind == "conv" for kind in c["layer_types"])


def attention_layers(c: dict) -> int:
    return len(c["layer_types"]) - conv_layers(c)


def expert_layers(c: dict) -> int:
    return len(c["layer_types"]) - c["num_dense_layers"]


def conv_params(c: dict) -> int:
    """A conv operator: ``in_proj`` (d -> 3 d), the taps, ``out_proj``."""
    d = c["hidden_size"]
    return 3 * d * d + c["conv_L_cache"] * d + d * d


def attention_params(c: dict) -> int:
    """q, k, v and the output projection (the head norms' scales are not
    counted, as no norm is)."""
    d, dim = c["hidden_size"], head_dim(c)
    qd, kvd = c["num_attention_heads"] * dim, c["num_key_value_heads"] * dim
    return d * (qd + 2 * kvd) + qd * d


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def params_outside_experts(c: dict) -> int:
    """Every parameter but the norms' scales and the routed experts: the
    operators, the leading dense SwiGLUs, the routers and their selection
    bias, the tied embedding once."""
    d = c["hidden_size"]
    router = d * c["num_experts"] + c["num_experts"]
    return (conv_layers(c) * conv_params(c)
            + attention_layers(c) * attention_params(c)
            + c["num_dense_layers"] * 3 * d * c["intermediate_size"]
            + expert_layers(c) * router + c["vocab_size"] * d)


def params(c: dict) -> int:
    """Every parameter but the norms' scales; the tied embedding once."""
    return params_outside_experts(c) \
        + expert_layers(c) * c["num_experts"] * expert_params(c)


def experts_hit(c: dict, tokens: float) -> float:
    """Experts an expert layer needs for a batch of ``tokens`` under uniform
    routing."""
    miss = 1.0 - c["num_experts_per_tok"] / c["num_experts"]
    return c["num_experts"] * (1.0 - miss ** tokens)


def kv_bytes_per_token(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """K and V of the attention layers, a token."""
    return attention_layers(c) * 2 * c["num_key_value_heads"] * head_dim(c) \
        * dtype_bytes


def state_bytes_per_slot(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """What the conv layers keep a sequence: ``conv_L_cache - 1`` gated
    inputs of ``hidden_size`` a layer, in the compute type."""
    return conv_layers(c) * (c["conv_L_cache"] - 1) * c["hidden_size"] \
        * dtype_bytes


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the lfm2 family has no trainer in this system: at 16 bytes a "
        "parameter one expert layer (614 M parameters) is 9.8 GB, so one chip "
        "holds an eighth of each layer's experts only (ISSUE 45)")


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None,
                      hit: float | None = None) -> float:
    """Bytes one decode iteration must move with ``slots`` sequences
    (default: the configuration's ``max_slots``) of ``live_kv_tokens``
    tokens in all: the weights outside the experts once, the experts hit
    (``hit``, summed over the expert layers; default the expectation under
    uniform routing) once, each sequence's tails read and written, the
    attention layers' K and V of every live token."""
    slots = slots or config["max_slots"]
    if hit is None:
        hit = expert_layers(config) * experts_hit(config, slots)
    weights = params_outside_experts(config) + hit * expert_params(config)
    return (weights * weight_dtype_bytes
            + 2.0 * slots * state_bytes_per_slot(config)
            + live_kv_tokens * kv_bytes_per_token(config, kv_dtype_bytes))


def decode_kernel(config: dict, name: str, lives,
                  observed: dict | None = None) -> dict:
    """``{"flops", "bytes"}`` one decode iteration requires of kernel family
    ``name`` with one sequence of each length in ``lives`` decoding.
    ``observed`` may hold the step log's means over the traced interval,
    ``moe_experts_hit`` and ``moe_pairs`` (both summed over the expert
    layers): which experts a batch needs is the router's doing, so what was
    needed is what was hit:

    - ``moe_grouped``: the hit experts' three matrices read once an expert
      layer, and the products of the routed pairs;
    - ``paged_attn``: in every attention layer, K and V of what each sequence
      attends read once, the queries in and the outputs out, and the score
      and value products of every query head;
    - ``decode_iter``: the whole iteration's bytes
      (:func:`decode_iter_bytes` with the true lengths and the hit
      experts)."""
    n, live = len(lives), float(sum(lives))
    layers = expert_layers(config)
    observed = observed or {}
    hit = observed.get("moe_experts_hit", layers * experts_hit(config, n))
    if name == "moe_grouped":
        pairs = observed.get(
            "moe_pairs", layers * n * config["num_experts_per_tok"])
        return {"flops": 2.0 * pairs * expert_params(config),
                "bytes": hit * expert_params(config) * DTYPE_BYTES}
    if name == "paged_attn":
        qd = config["num_attention_heads"] * head_dim(config)
        attn = attention_layers(config)
        return {"flops": attn * live * 4.0 * qd,
                "bytes": live * kv_bytes_per_token(config)
                + attn * n * 2.0 * qd * DTYPE_BYTES}
    if name == "decode_iter":
        return {"flops": 0.0,
                "bytes": decode_iter_bytes(config, live, DTYPE_BYTES,
                                           slots=n, hit=hit)}
    raise KeyError(f"counts/lfm2.py has no decode kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The same requirement at the configuration's nominal decode batch
    (``nominal_decode``: ``slots`` sequences of ``live_tokens`` each), for
    callers that know no lengths."""
    nominal = config["nominal_decode"]
    return decode_kernel(config, name,
                         [nominal["live_tokens"]] * nominal["slots"])
