"""Required operations and bytes of the Ouro looped decoder (the whole stack
of ``num_hidden_layers`` layers run ``total_ut_steps`` times over the same
weights, MHA with rotary, a SwiGLU, sandwich norms, an untied head), from
shapes alone.

The yardstick every roofline share of a ``"counts": "ouro"`` configuration
divides by.  Convention as in ``counts/gpt2.py``: one multiply-add is 2
FLOPs, only what the algorithm *requires* is counted.  Shapes come from the
configuration file's top level (the published keys: ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``intermediate_size``, ``num_hidden_layers``, ``total_ut_steps``,
``vocab_size``).

**The loop is the requirement.**  A pass of the stack reads every layer's
weights, and the next pass needs the last layer's output of this one: the
passes cannot share a read of a layer's weights unless all of them (4.93 GB
at the published widths) stayed on the chip between passes, which no v5e
holds.  So a decode iteration must stream the layers' weights ``total_ut_steps``
times (19.7 GB), the head once and the embedding's rows it looks up (not
counted: a row a sequence); pass ``u`` of a layer attends the K and V pass
``u`` wrote, so a live token's rows are read in all ``total_ut_steps x
num_hidden_layers`` layer slots (1,572,864 B a token at the published widths),
and the step's new token's rows are written to every slot.

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for readers
``trace_decode_kernel`` and ``trace_scope_roofline``, which know every live
sequence's length and the step log's counters.
"""

from __future__ import annotations

DTYPE_BYTES = 2


def passes(c: dict) -> int:
    return c["total_ut_steps"]


def layer_slots(c: dict) -> int:
    """Layer slots a token keeps K and V in: a slot a pass a layer."""
    return passes(c) * c["num_hidden_layers"]


def attention_params(c: dict) -> int:
    d, dim = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return d * h * dim + 2 * d * kv * dim + h * dim * d


def ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_params(c: dict) -> int:
    """One layer's matrices (its four norms' scales are not counted, as no
    norm is)."""
    return attention_params(c) + ffn_params(c)


def head_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def params(c: dict) -> int:
    """Every parameter but the norms' scales and the exit gate: the layers
    once, the embedding and the untied head."""
    return c["num_hidden_layers"] * layer_params(c) + 2 * head_params(c)


def params_exact(c: dict) -> int:
    """Every parameter of the published model: :func:`params`, four norm
    scales a layer, the final norm, the exit gate's weight and bias."""
    d = c["hidden_size"]
    return params(c) + c["num_hidden_layers"] * 4 * d + d + d + 1


def kv_bytes_per_token(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """K and V of every layer slot, a token."""
    return layer_slots(c) * 2 * c["num_key_value_heads"] * c["head_dim"] \
        * dtype_bytes


def flops_per_token(c: dict, context: float = 0.0) -> float:
    """Forward operations a token: the layers' matrices ``total_ut_steps``
    times, the head once, and both attention products over ``context`` keys
    in every layer slot."""
    return (2.0 * passes(c) * c["num_hidden_layers"] * layer_params(c)
            + 2.0 * head_params(c)
            + layer_slots(c) * context * c["num_attention_heads"] * 4.0
            * c["head_dim"])


def chunk_attention(c: dict, pairs: float, queries: float) -> dict:
    """``{"flops", "bytes"}`` the attention of all layer slots requires for
    one prefill chunk of ``queries`` real queries that attend ``pairs``
    (query, key) pairs a slot: both products of every query head, K and V of
    the context once a K/V head, the queries in and the outputs out."""
    h, dim = c["num_attention_heads"], c["head_dim"]
    slots = layer_slots(c)
    context = pairs / max(queries, 1.0) + queries / 2.0     # its last row
    return {"flops": slots * pairs * h * 4.0 * dim,
            "bytes": context * kv_bytes_per_token(c)
            + slots * queries * 2 * h * dim * DTYPE_BYTES}


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the ouro family has no trainer in this system: the loss over exit "
        "steps has hyperparameters no key of config.json gives (ISSUE 61)")


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None) -> float:
    """Bytes one decode iteration must move with ``slots`` sequences
    (default: the configuration's ``max_slots``) of ``live_kv_tokens`` tokens
    in all: the layers' weights once a PASS, the head once, the K and V of
    every live token in every layer slot, and the new tokens' rows written
    to every slot."""
    slots = slots or config["max_slots"]
    c = config
    weights = (passes(c) * c["num_hidden_layers"] * layer_params(c)
               + head_params(c))
    return (weights * weight_dtype_bytes
            + (live_kv_tokens + slots)
            * kv_bytes_per_token(c, kv_dtype_bytes))


def decode_kernel(config: dict, name: str, lives,
                  observed: dict | None = None) -> dict:
    """``{"flops", "bytes"}`` one decode iteration requires of kernel family
    ``name`` with one sequence of each length in ``lives`` decoding:

    - ``paged_attn``: over the ``total_ut_steps x num_hidden_layers`` calls of
      a step, K and V of what each sequence attends read once a layer slot,
      the queries in and the outputs out, and the score and value products
      of every query head;
    - ``decode_iter``: the whole iteration's bytes
      (:func:`decode_iter_bytes` with the true lengths);
    - ``kv_chunk_attn``: not of a decode iteration but of one execution of the
      prefill program, whatever ``lives``: the attention of a chunk's real
      queries over the pairs they attend, ``observed``'s ``chunk_pairs`` and
      ``chunk_tokens`` over ``prefill_chunks`` (a prompt's last chunk is part
      padding, and a pad position is no required work), in all layer slots; a
      whole chunk from an empty context where the log has none."""
    n, live = len(lives), float(sum(lives))
    observed = observed or {}
    if name == "kv_chunk_attn":
        chunks = observed.get("prefill_chunks")
        tokens = (observed["chunk_tokens"] / chunks if chunks
                  else config["prefill_chunk"])
        pairs = (observed["chunk_pairs"] / chunks if chunks
                 else tokens * (tokens + 1) / 2.0)
        return chunk_attention(config, pairs, tokens)
    if name == "paged_attn":
        h, dim = config["num_attention_heads"], config["head_dim"]
        return {"flops": layer_slots(config) * live * h * 4.0 * dim,
                "bytes": live * kv_bytes_per_token(config)
                + layer_slots(config) * n * 2 * h * dim * DTYPE_BYTES}
    if name == "decode_iter":
        return {"flops": 0.0,
                "bytes": decode_iter_bytes(config, live, DTYPE_BYTES,
                                           slots=n)}
    raise KeyError(f"counts/ouro.py has no decode kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The requirement of one execution of a program, for callers that know
    no lengths: ``kv_chunk_attn`` of one prefill chunk of real tokens from
    an empty context; any other name at the configuration's nominal decode
    batch (``nominal_decode``)."""
    if name == "kv_chunk_attn":
        return decode_kernel(config, name, [])
    nominal = config["nominal_decode"]
    return decode_kernel(config, name,
                         [nominal["live_tokens"]] * nominal["slots"])
