"""Required operations and bytes of the GLM-5 decoder (latent attention whose
rows a learned indexer selects, 256 sigmoid-routed experts top 8 + 1 shared)
as one chip's share of a 16-chip expert-parallel deployment holds it, from
shapes alone.

The yardstick every roofline share of a ``"counts": "glm5"`` configuration
divides by.  Convention as in ``counts/gpt2.py``: one multiply-add is 2
FLOPs, only what the algorithm *requires* is counted.  Shapes come from the
configuration file's top level (the published keys: ``hidden_size``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``index_n_heads``, ``index_head_dim``, ``index_topk``, ``intermediate_size``,
``moe_intermediate_size``, ``n_routed_experts`` — the experts *held* —,
``n_routed_experts_published``, ``n_shared_experts``,
``num_experts_per_tok``, ``num_hidden_layers``, ``first_k_dense_replace``,
``vocab_size``).

What one decode iteration must read: the weights outside the routed experts
once whatever the batch; each held expert *that some token of the batch is
routed to* once; of every live token one index key a layer
(``index_head_dim`` values, 256 bytes in bf16: the indexer scores them all);
of every *selected* token — ``min(index_topk, length)`` a sequence a layer —
one latent row, ``kv_lora_rank + qk_rope_head_dim`` values *as laid out*,
five lane tiles = 1,280 bytes: a row is gathered by index, whole tiles at a
time, so the 64 zero lanes are read with it.  What it must compute: for
every scored key ``2 * index_n_heads * index_head_dim`` FLOPs; for every
head and selected row, in the absorbed form, the score over the row's 576
values and the weighted sum of its first 512: ``2 * heads * (576 + 512)``.

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for reader
``trace_decode_kernel``, which knows every live sequence's length and the
step log's routing counters.
"""

from __future__ import annotations

DTYPE_BYTES = 2
LANES = 128


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


def indexer_params(c: dict) -> int:
    """The index queries' projection off the query latent, the one index
    key's and the head weights' off the block's input."""
    hi, di = c["index_n_heads"], c["index_head_dim"]
    return c["q_lora_rank"] * hi * di + c["hidden_size"] * (di + hi)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def params_outside_experts(c: dict) -> int:
    """Matmul parameters every token uses: the attention projections and the
    indexer of every layer, the dense SwiGLU of the leading layers, the
    shared expert and the router (its published width) of the others, the
    output head over the vocabulary held (the embedding lookup is a
    gather)."""
    d = c["hidden_size"]
    dense = 3 * d * c["intermediate_size"]
    shared = c.get("n_shared_experts", 1) * expert_params(c)
    router = d * c["n_routed_experts_published"]
    return (c["num_hidden_layers"] * (attention_params(c) + indexer_params(c))
            + c["first_k_dense_replace"] * dense
            + _expert_layers(c) * (shared + router) + d * c["vocab_size"])


def matmul_params(c: dict) -> int:
    """Every matrix this share holds: the above, the held experts and the
    embedding (norm scales, the selection bias and the index key's LayerNorm
    are vectors and are not counted)."""
    return (params_outside_experts(c)
            + _expert_layers(c) * c["n_routed_experts"] * expert_params(c)
            + c["hidden_size"] * c["vocab_size"])


def experts_hit(c: dict, tokens: float) -> float:
    """Held experts an expert layer needs for a batch of ``tokens`` under
    uniform routing over the published experts."""
    miss = 1.0 - c["num_experts_per_tok"] / c["n_routed_experts_published"]
    return c["n_routed_experts"] * (1.0 - miss ** tokens)


def latent_row_bytes(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """Bytes of a latent row as it is read: ``[c_kv | k_rope]`` in whole
    lane tiles."""
    values = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return -(-values // LANES) * LANES * dtype_bytes


def index_key_bytes(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    return c["index_head_dim"] * dtype_bytes


def cache_bytes_per_token_layer(c: dict,
                                dtype_bytes: int = DTYPE_BYTES) -> int:
    """As laid out: the latent row's five lane tiles and the index key."""
    return latent_row_bytes(c, dtype_bytes) + index_key_bytes(c, dtype_bytes)


def scored_rows(c: dict, lives) -> float:
    """Index keys scored over all layers for sequences of ``lives`` tokens."""
    return float(c["num_hidden_layers"] * sum(lives))


def selected_rows(c: dict, lives) -> float:
    """Latent rows attended over all layers: ``index_topk`` a sequence a
    layer at most."""
    return float(c["num_hidden_layers"]
                 * sum(min(n, c["index_topk"]) for n in lives))


def latent_attn_flops_per_row(c: dict) -> float:
    """Absorbed form, a head a selected row: the score over the row, the
    weighted sum of its ``c_kv``."""
    return 2.0 * c["num_attention_heads"] * (
        2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])


def index_flops_per_row(c: dict) -> float:
    return 2.0 * c["index_n_heads"] * c["index_head_dim"]


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the joyai family (GLM-5 is it with the indexer on) has no trainer "
        "in this system: latent attention has no backward here (ISSUE 39)")


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None) -> float:
    """Bytes one decode iteration must read with ``slots`` sequences
    (default: the configuration's ``max_slots``) of ``live_kv_tokens``
    tokens in all: non-expert weights once, the expected held experts hit
    once a layer, an index key a live token a layer and a latent row a
    selected token a layer (each sequence taken as the mean length)."""
    slots = slots or config["max_slots"]
    mean = live_kv_tokens / max(slots, 1)
    weights = params_outside_experts(config) + _expert_layers(config) * \
        experts_hit(config, slots) * expert_params(config)
    picked = slots * min(mean, config["index_topk"])
    return weights * weight_dtype_bytes + config["num_hidden_layers"] * (
        live_kv_tokens * index_key_bytes(config, kv_dtype_bytes)
        + picked * latent_row_bytes(config, kv_dtype_bytes))


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
    - ``index_scores``: an index key of every cached token a layer (256 B),
      ``2 * 32 * 128`` FLOPs each;
    - ``sparse_latent_attn``: a latent row of every selected token a layer
      (1,280 B as laid out), the queries in (a head a slot a layer, 640
      values) and the latent outputs out (512, float32), and the absorbed
      score and value products, ``2 * 64 * (576 + 512)`` FLOPs a row;
    - ``decode_iter``: the whole iteration's bytes (:func:`decode_iter_bytes`
      with the true lengths and the observed experts)."""
    n = len(lives)
    layers = _expert_layers(config)
    observed = observed or {}
    hit = observed.get("moe_experts_hit", layers * experts_hit(config, n))
    if name == "moe_grouped":
        pairs = observed.get(
            "moe_pairs", layers * n * config["num_experts_per_tok"]
            * config["n_routed_experts"]
            / config["n_routed_experts_published"])
        return {"flops": 2.0 * pairs * expert_params(config),
                "bytes": hit * expert_params(config) * DTYPE_BYTES}
    scored = scored_rows(config, lives)
    picked = selected_rows(config, lives)
    if name == "index_scores":
        per_query = config["index_n_heads"] * config["index_head_dim"]
        return {"flops": scored * index_flops_per_row(config),
                "bytes": scored * index_key_bytes(config)
                + config["num_hidden_layers"] * n * per_query * DTYPE_BYTES}
    if name == "sparse_latent_attn":
        h = config["num_attention_heads"]
        per_query = h * (latent_row_bytes(config)
                         + 4 * config["kv_lora_rank"])
        return {"flops": picked * latent_attn_flops_per_row(config),
                "bytes": picked * latent_row_bytes(config)
                + config["num_hidden_layers"] * n * per_query}
    if name == "decode_iter":
        weights = params_outside_experts(config) \
            + hit * expert_params(config)
        return {"flops": 0.0, "bytes": weights * DTYPE_BYTES
                + scored * index_key_bytes(config)
                + picked * latent_row_bytes(config)}
    raise KeyError(f"counts/glm5.py has no kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The same requirement at the configuration's nominal decode batch
    (``nominal_decode``: ``slots`` sequences of ``live_tokens`` each), for
    callers that know no lengths."""
    nominal = config["nominal_decode"]
    return decode_kernel(config, name,
                         [nominal["live_tokens"]] * nominal["slots"])
