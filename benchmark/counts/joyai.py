"""Required operations and bytes of the JoyAI-LLM-Flash decoder (latent
attention, 256 sigmoid-routed experts top 8 + 1 shared) as this cut holds
it, from shapes alone.

The yardstick every roofline share of a ``"counts": "joyai"`` configuration
divides by.  Convention as in ``counts/gpt2.py``: one multiply-add is 2
FLOPs, only what the algorithm *requires* is counted.  Shapes come from the
configuration file's top level (the published keys: ``hidden_size``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``intermediate_size``, ``moe_intermediate_size``, ``n_routed_experts``,
``n_shared_experts``, ``num_experts_per_tok``, ``num_hidden_layers``,
``first_k_dense_replace``, ``vocab_size``).

What one decode iteration must read: the weights outside the routed experts
once whatever the batch; each expert *that some token of the batch is routed
to* once; of every live token one latent row a layer, ``kv_lora_rank +
qk_rope_head_dim`` values (1,152 bytes in bf16: the row is stored padded to
five lane tiles, 1,280 bytes, which the requirement does not count).  What
latent attention must compute in the absorbed form the kernel takes: for
every head and attended row, the score over the row's 576 values and the
weighted sum of its first 512: ``2 * heads * (576 + 512)`` FLOPs.

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for reader
``trace_decode_kernel``, which knows every live sequence's length and the
step log's routing counters.
"""

from __future__ import annotations

DTYPE_BYTES = 2


def _expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def attention_params(c: dict) -> int:
    """q_a, q_b, kv_a, kv_b (both halves) and the output projection."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    rank = c["kv_lora_rank"]
    return (d * c["q_lora_rank"]
            + c["q_lora_rank"] * h * (c["qk_nope_head_dim"]
                                      + c["qk_rope_head_dim"])
            + d * (rank + c["qk_rope_head_dim"])
            + rank * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def params_outside_experts(c: dict) -> int:
    """Matmul parameters every token uses: the attention projections of
    every layer, the dense SwiGLU of the leading layers, the shared expert
    and the router of the others, the output head (the embedding lookup is
    a gather)."""
    d = c["hidden_size"]
    dense = 3 * d * c["intermediate_size"]
    shared = c.get("n_shared_experts", 1) * expert_params(c)
    router = d * c["n_routed_experts"]
    return (c["num_hidden_layers"] * attention_params(c)
            + c["first_k_dense_replace"] * dense
            + _expert_layers(c) * (shared + router) + d * c["vocab_size"])


def experts_hit(c: dict, tokens: float) -> float:
    """Experts an expert layer needs for a batch of ``tokens`` under
    uniform routing."""
    miss = 1.0 - c["num_experts_per_tok"] / c["n_routed_experts"]
    return c["n_routed_experts"] * (1.0 - miss ** tokens)


def latent_row_bytes(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """Bytes cached a token a layer: ``c_kv`` and the shared ``k_rope``."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * dtype_bytes


def attended_rows(c: dict, lives) -> float:
    """Latent rows read over all layers for sequences of ``lives`` tokens."""
    return float(c["num_hidden_layers"] * sum(lives))


def latent_attn_flops_per_row(c: dict) -> float:
    """Absorbed form, a head an attended row: the score over the row, the
    weighted sum of its ``c_kv``."""
    return 2.0 * c["num_attention_heads"] * (
        2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the joyai family has no trainer in this system: latent attention "
        "has no backward here, and at 16 bytes a parameter the cut that "
        "serves (5,558 M parameters) is 89 GB (ISSUE 32)")


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None) -> float:
    """Bytes one decode iteration must read with ``slots`` sequences
    (default: the configuration's ``max_slots``) of ``live_kv_tokens``
    tokens in all: non-expert weights once, the expected experts hit once a
    layer, one latent row a live token a layer."""
    slots = slots or config["max_slots"]
    weights = params_outside_experts(config) + _expert_layers(config) * \
        experts_hit(config, slots) * expert_params(config)
    return weights * weight_dtype_bytes + config["num_hidden_layers"] \
        * live_kv_tokens * latent_row_bytes(config, kv_dtype_bytes)


def decode_kernel(config: dict, name: str, lives,
                  observed: dict | None = None) -> dict:
    """``{"flops", "bytes"}`` one decode iteration requires of kernel
    family ``name`` with one sequence of each length in ``lives`` decoding.
    ``observed`` may hold the step log's means over the traced interval,
    ``moe_experts_hit`` and ``moe_pairs`` (both summed over the expert
    layers): which experts a batch needs is the router's doing, so what was
    needed is what was hit:

    - ``moe_grouped``: the hit experts' three matrices read once an expert
      layer, and the products of the routed pairs;
    - ``paged_latent_attn``: the latent row of what each sequence attends in
      each layer, the queries in (a head a slot a layer, 576 values) and the
      latent outputs out (512), and the absorbed score and value products;
    - ``decode_iter``: the whole iteration's bytes (:func:`decode_iter_bytes`
      with the true lengths and the observed experts)."""
    n = len(lives)
    layers = _expert_layers(config)
    observed = observed or {}
    hit = observed.get("moe_experts_hit", layers * experts_hit(config, n))
    if name == "moe_grouped":
        pairs = observed.get(
            "moe_pairs", layers * n * config["num_experts_per_tok"])
        return {"flops": 2.0 * pairs * expert_params(config),
                "bytes": hit * expert_params(config) * DTYPE_BYTES}
    rows = attended_rows(config, lives)
    if name == "paged_latent_attn":
        per_query = config["num_attention_heads"] * (
            2 * config["kv_lora_rank"] + config["qk_rope_head_dim"])
        return {"flops": rows * latent_attn_flops_per_row(config),
                "bytes": rows * latent_row_bytes(config)
                + config["num_hidden_layers"] * n * per_query * DTYPE_BYTES}
    if name == "decode_iter":
        weights = params_outside_experts(config) \
            + hit * expert_params(config)
        return {"flops": 0.0, "bytes": weights * DTYPE_BYTES
                + rows * latent_row_bytes(config)}
    raise KeyError(f"counts/joyai.py has no kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The same requirement at the configuration's nominal decode batch
    (``nominal_decode``: ``slots`` sequences of ``live_tokens`` each), for
    callers that know no lengths."""
    nominal = config["nominal_decode"]
    return decode_kernel(config, name,
                         [nominal["live_tokens"]] * nominal["slots"])
