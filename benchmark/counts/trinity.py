"""Required operations and bytes of the afmoe decoder (Arcee Trinity) as one
chip's share holds it, from shapes alone.

The yardstick every roofline share of a ``"counts": "trinity"``
configuration divides by.  Convention as in ``counts/gpt2.py``: one
multiply-add is 2 FLOPs, only what the algorithm *requires* is counted.
Shapes come from the configuration file's top level (the published keys:
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``, ``moe_intermediate_size``,
``num_experts`` — the experts *held* —, ``num_experts_published``,
``num_experts_per_tok``, ``layer_types``, ``num_dense_layers``,
``sliding_window``, ``vocab_size``).

What one decode iteration must read: the weights outside the routed experts
once whatever the batch; each held expert *that some token of the batch is
routed to* once (an expert nobody chose is not needed); the K/V of every
live token, on a window layer no more of a sequence than the window.  Which
experts are hit depends on the weights; the requirement uses the expectation
under uniform routing, ``held x (1 - (1 - k / published) ** tokens)``.

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for reader
``trace_decode_kernel``, which knows every live sequence's length and the
step log's routing counters.
"""

from __future__ import annotations

DTYPE_BYTES = 2


def _widths(c: dict) -> tuple[int, int, int]:
    return (c["hidden_size"], c["num_attention_heads"] * c["head_dim"],
            c["num_key_value_heads"] * c["head_dim"])


def _expert_layers(c: dict) -> int:
    return len(c["layer_types"]) - c["num_dense_layers"]


def params_outside_experts(c: dict) -> int:
    """Matmul parameters every token uses: q, k, v, gate and output
    projections of every layer, the dense SwiGLU of the leading layers, the
    shared expert and the router of the others, the output head (the
    embedding lookup is a gather)."""
    d, qd, kvd = _widths(c)
    attn = d * (2 * qd + 2 * kvd) + qd * d
    dense = 3 * d * c["intermediate_size"]
    shared = c.get("num_shared_experts", 1) * 3 * d * c["moe_intermediate_size"]
    router = d * c["num_experts_published"]
    return (len(c["layer_types"]) * attn + c["num_dense_layers"] * dense
            + _expert_layers(c) * (shared + router) + d * c["vocab_size"])


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def experts_hit(c: dict, tokens: float) -> float:
    """Held experts an expert layer needs for a batch of ``tokens``."""
    miss = 1.0 - c["num_experts_per_tok"] / c["num_experts_published"]
    return c["num_experts"] * (1.0 - miss ** tokens)


def kv_bytes_per_token_layer(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    return 2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes


def attended_tokens(c: dict, lives) -> float:
    """Keys read over all layers for sequences of ``lives`` tokens each."""
    w = c["sliding_window"]
    return float(sum(
        sum(min(n, w) if kind == "sliding_attention" else n for n in lives)
        for kind in c["layer_types"]))


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the afmoe family has no trainer in this system: at 16 bytes a "
        "parameter the cut that serves (4,322 M parameters) is 69 GB, and "
        "no cut inside the guide's floors trains on one chip (ISSUE 28)")


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None) -> float:
    """Bytes one decode iteration must read with ``slots`` sequences
    (default: the configuration's ``max_slots``) of ``live_kv_tokens``
    tokens in all, taken as equally long: non-expert weights once, the
    expected held experts hit once a layer, K/V of the live tokens capped
    at the window on window layers."""
    slots = slots or config["max_slots"]
    lives = [live_kv_tokens / slots] * slots
    weights = params_outside_experts(config) + _expert_layers(config) * \
        experts_hit(config, slots) * expert_params(config)
    return weights * weight_dtype_bytes + attended_tokens(config, lives) \
        * kv_bytes_per_token_layer(config, kv_dtype_bytes)


def decode_kernel(config: dict, name: str, lives,
                  observed: dict | None = None) -> dict:
    """``{"flops", "bytes"}`` one decode iteration requires of kernel
    family ``name`` with one sequence of each length in ``lives`` decoding.
    ``observed`` may hold the step log's means over the traced interval,
    ``moe_experts_hit`` and ``moe_pairs`` (both summed over the expert
    layers): which experts a batch needs is the router's doing, and a
    random router is far from uniform (39 % of the held experts hit where
    uniform routing gives 64 %), so what was needed is what was hit:

    - ``moe_grouped``: the expected hit experts' three matrices read once an
      expert layer, and the products of the expected routed pairs;
    - ``paged_attn``: K and V of what each sequence attends in each layer,
      and the score and value products over them;
    - ``decode_iter``: the whole iteration's bytes (:func:`decode_iter_bytes`
      with the true lengths)."""
    n = len(lives)
    layers = _expert_layers(config)
    observed = observed or {}
    hit = observed.get("moe_experts_hit", layers * experts_hit(config, n))
    if name == "moe_grouped":
        pairs = observed.get("moe_pairs", layers * n * config[
            "num_experts_per_tok"] * config["num_experts"]
            / config["num_experts_published"])
        return {"flops": 2.0 * pairs * expert_params(config),
                "bytes": hit * expert_params(config) * DTYPE_BYTES}
    keys = attended_tokens(config, lives)
    if name == "paged_attn":
        qd = config["num_attention_heads"] * config["head_dim"]
        return {"flops": 4.0 * qd * keys,
                "bytes": keys * kv_bytes_per_token_layer(config)}
    if name == "decode_iter":
        weights = params_outside_experts(config) \
            + hit * expert_params(config)
        return {"flops": 0.0, "bytes": weights * DTYPE_BYTES
                + keys * kv_bytes_per_token_layer(config)}
    raise KeyError(f"counts/trinity.py has no kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The same requirement at the configuration's nominal decode batch
    (``nominal_decode``: ``slots`` sequences of ``live_tokens`` each), for
    callers that know no lengths."""
    nominal = config["nominal_decode"]
    return decode_kernel(config, name,
                         [nominal["live_tokens"]] * nominal["slots"])
