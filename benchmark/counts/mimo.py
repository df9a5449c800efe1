"""Required operations and bytes of the MiMo-V2 decoder (Xiaomi MiMo-V2.5's
language model) as one chip's share holds it, from shapes alone.

The yardstick every roofline share of a ``"counts": "mimo"`` configuration
divides by.  Convention as in ``counts/gpt2.py``: one multiply-add is 2
FLOPs, only what the algorithm *requires* is counted.  Shapes come from the
configuration file's top level (the published keys: ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads`` and
``swa_num_key_value_heads``, ``head_dim``, ``v_head_dim``,
``intermediate_size``, ``moe_intermediate_size``, ``n_routed_experts`` — the
experts *held* —, ``n_routed_experts_published``, ``num_experts_per_tok``,
``hybrid_layer_pattern`` (0 full, 1 window), ``moe_layer_freq``,
``sliding_window``, ``vocab_size``).

What one decode iteration must read: the weights outside the routed experts
once whatever the batch (the embedding is a gather of a row a token); each
held expert *that some token of the batch is routed to* once; K and V of
every live token of a full layer (``num_key_value_heads x (head_dim +
v_head_dim)`` values: 2,560 B), and of a window layer no more of a sequence
than the window (``swa_num_key_value_heads x ...``: 5,120 B a row).  The
values are counted as stored, without lane padding: the layout is the
program's, not the algorithm's.

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for readers
``trace_decode_kernel`` and ``trace_decode_scope``, which know every live
sequence's length and the step log's routing counters.
"""

from __future__ import annotations

DTYPE_BYTES = 2


def _kv_heads(c: dict, kind: int) -> int:
    return c["swa_num_key_value_heads" if kind else "num_key_value_heads"]


def attention_params(c: dict, kind: int) -> int:
    """q, k, v and output projections of a layer of ``kind`` (0 full, 1
    window), and the window kind's sink a head."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    kv = _kv_heads(c, kind)
    sinks = h if kind and c["add_swa_attention_sink_bias"] else 0
    return (d * (h + kv) * c["head_dim"] + d * kv * c["v_head_dim"]
            + h * c["v_head_dim"] * d + sinks)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _expert_layers(c: dict) -> int:
    return sum(1 for f in c["moe_layer_freq"] if f)


def params_outside_experts(c: dict) -> int:
    """Matmul parameters every token uses: the attention projections of
    every layer, the dense SwiGLU of the layers without experts, the router
    of the others, the output head (the embedding lookup is a gather)."""
    d = c["hidden_size"]
    layers = len(c["hybrid_layer_pattern"])
    return (sum(attention_params(c, k) for k in c["hybrid_layer_pattern"])
            + (layers - _expert_layers(c)) * 3 * d * c["intermediate_size"]
            + _expert_layers(c) * d * c["n_routed_experts_published"]
            + d * c["vocab_size"])


def weight_params(c: dict) -> int:
    """Every parameter the share holds: the above, the held experts with
    their selection bias, the embedding and the norms."""
    d = c["hidden_size"]
    layers = len(c["hybrid_layer_pattern"])
    return (params_outside_experts(c)
            + _expert_layers(c) * (c["n_routed_experts"] * expert_params(c)
                                   + c["n_routed_experts_published"])
            + d * c["vocab_size"] + (2 * layers + 1) * d)


def experts_hit(c: dict, tokens: float) -> float:
    """Held experts an expert layer needs for a batch of ``tokens``."""
    miss = 1.0 - c["num_experts_per_tok"] / c["n_routed_experts_published"]
    return c["n_routed_experts"] * (1.0 - miss ** tokens)


def kv_bytes_per_token_layer(c: dict, kind: int,
                             dtype_bytes: int = DTYPE_BYTES) -> int:
    """K and V a token a layer of ``kind``, as stored values."""
    return _kv_heads(c, kind) * (c["head_dim"] + c["v_head_dim"]) \
        * dtype_bytes


def attended_rows(c: dict, lives) -> dict[int, float]:
    """``{kind: keys read over that kind's layers}`` for sequences of
    ``lives`` tokens each."""
    w = c["sliding_window"]
    rows = {0: 0.0, 1: 0.0}
    for kind in c["hybrid_layer_pattern"]:
        rows[kind] += float(sum(min(n, w) if kind else n for n in lives))
    return rows


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the mimo family has no trainer in this system: at 16 bytes a "
        "parameter a dense layer, one period of six expert layers at 8 held "
        "experts and an eighth of the vocabulary are 35.6 GB (ISSUE 41)")


def _kv_bytes(c: dict, lives, dtype_bytes: int = DTYPE_BYTES) -> float:
    return sum(rows * kv_bytes_per_token_layer(c, kind, dtype_bytes)
               for kind, rows in attended_rows(c, lives).items())


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None) -> float:
    """Bytes one decode iteration must read with ``slots`` sequences
    (default: the configuration's ``max_slots``) of ``live_kv_tokens``
    tokens in all, taken as equally long."""
    slots = slots or config["max_slots"]
    lives = [live_kv_tokens / slots] * slots
    weights = params_outside_experts(config) + _expert_layers(config) * \
        experts_hit(config, slots) * expert_params(config)
    return weights * weight_dtype_bytes \
        + _kv_bytes(config, lives, kv_dtype_bytes)


def decode_kernel(config: dict, name: str, lives,
                  observed: dict | None = None) -> dict:
    """``{"flops", "bytes"}`` one decode iteration requires of kernel
    family ``name`` with one sequence of each length in ``lives`` decoding.
    ``observed`` may hold the step log's means over the traced interval,
    ``moe_experts_hit`` and ``moe_pairs`` (both summed over the expert
    layers): what was needed is what was hit.

    - ``moe_grouped``: the hit experts' three matrices read once an expert
      layer, and the products of the routed pairs;
    - ``paged_attn``: K and V of what each sequence attends in each layer
      (2,560 B a row of a full layer, 5,120 B of a window layer, ``min(len,
      window)`` rows of it), and the score (``head_dim`` wide) and value
      (``v_head_dim`` wide) products of every query head over them;
    - ``paged_attn_full`` / ``paged_attn_window``: the same of one kind's
      layers;
    - ``decode_iter``: the whole iteration's bytes."""
    c = config
    n = len(lives)
    layers = _expert_layers(c)
    observed = observed or {}
    hit = observed.get("moe_experts_hit", layers * experts_hit(c, n))
    if name == "moe_grouped":
        pairs = observed.get("moe_pairs", layers * n * c[
            "num_experts_per_tok"] * c["n_routed_experts"]
            / c["n_routed_experts_published"])
        return {"flops": 2.0 * pairs * expert_params(c),
                "bytes": hit * expert_params(c) * DTYPE_BYTES}
    rows = attended_rows(c, lives)
    kinds = {"paged_attn": (0, 1), "paged_attn_full": (0,),
             "paged_attn_window": (1,)}.get(name)
    if kinds is not None:
        per_row = 2.0 * c["num_attention_heads"] * (c["head_dim"]
                                                    + c["v_head_dim"])
        return {"flops": sum(per_row * rows[k] for k in kinds),
                "bytes": sum(rows[k] * kv_bytes_per_token_layer(c, k)
                             for k in kinds)}
    if name == "decode_iter":
        weights = params_outside_experts(c) + hit * expert_params(c)
        return {"flops": 0.0,
                "bytes": weights * DTYPE_BYTES + _kv_bytes(c, lives)}
    raise KeyError(f"counts/mimo.py has no kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The same requirement at the configuration's nominal decode batch
    (``nominal_decode``: ``slots`` sequences of ``live_tokens`` each), for
    callers that know no lengths."""
    nominal = config["nominal_decode"]
    return decode_kernel(config, name,
                         [nominal["live_tokens"]] * nominal["slots"])
