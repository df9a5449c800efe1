"""Required operations and bytes of Qwen3-Next-80B-A3B-Instruct's language
model (``qwen3_next``: three Gated DeltaNet layers to one gated-attention
layer, every layer softmax-routed experts of which a chip holds a quarter and a
gated shared expert), from shapes alone.

The yardstick every roofline share of a ``"counts": "qwen3_next"``
configuration divides by.  Convention as in ``counts/gpt2.py``: one
multiply-add is 2 FLOPs, only what the algorithm *requires* is counted,
whatever implements it.  Shapes come from the configuration file's top level
(the published keys: ``hidden_size``, ``num_hidden_layers``,
``full_attention_interval``, ``linear_num_key_heads``,
``linear_num_value_heads``, ``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``moe_intermediate_size``, ``shared_expert_intermediate_size``,
``num_experts`` — the experts *held* —, ``num_experts_published``,
``num_experts_per_tok``, ``vocab_size``).

**The gated delta rule**, a token a *value* head a Gated DeltaNet layer, over a
state of ``K x V`` float32 values (``K = linear_key_head_dim``, ``V =
linear_value_head_dim``): the decay of every value (1), the prediction ``S'^T
k`` (2), the rank-one correction (2) and the read ``S^T q`` (2): ``7 K V``
FLOPs, as ``counts/ling.py`` counts KDA's — the gate's form changes no
required operation —, whether a step computes them on the vector unit or a
chunk of 64 as products (the chunked form spends more, on the matrix unit: its
triangular solve and its decay ratios are not required operations).  Bytes of
a decode step (kernel ``kda_step``): the state read once and written once, and
a value head's rows ``v`` in and ``o`` out with ``g`` and ``beta``, a key
head's ``q`` and ``k`` once, float32 as the recurrence computes them.  Bytes
of a prefill chunk's scan (scope ``gdn/scan``): the same rows a token, the
state once in and once out a chunk.

**Attention at a head of 256**: a decode step reads K and V of what each
sequence attends once (2,048 B a token a layer), the queries in and the
outputs out, ``4 D`` FLOPs a query head a key; a prefill chunk's kernel
(``kv_chunk_attn``) the same products over the causal pairs of its *real*
queries (the step log's ``chunk_pairs``), K and V of its context once a query
head group the kernel holds — counted once a K/V head: the least.

**A decode iteration** must read every weight outside the routed experts once
whatever the batch, each held expert *that some token of the batch is routed
to* once, read *and write* the state of every live sequence
(``state_bytes_per_slot``), and read of every live token the K/V rows of the
attention layers (``kv_bytes_per_token``: 4,096 bytes at the published widths
and this depth).

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for readers
``trace_decode_kernel`` and ``trace_scope_roofline``, and ``published_params``
/ ``published_active_params``: the whole model's count from the published keys
(79.67 B, 3.3 B a token beside the head), which ``tests/test_qwen3_next.py``
holds to the name "80B-A3B".
"""

from __future__ import annotations

DTYPE_BYTES = 2
STATE_BYTES = 4


def attention_layers(c: dict) -> int:
    return c["num_hidden_layers"] // c["full_attention_interval"]


def gdn_layers(c: dict) -> int:
    return c["num_hidden_layers"] - attention_layers(c)


def conv_channels(c: dict) -> int:
    return (2 * c["linear_num_key_heads"] * c["linear_key_head_dim"]
            + c["linear_num_value_heads"] * c["linear_value_head_dim"])


def gdn_params(c: dict) -> int:
    """A Gated DeltaNet mixer: ``W_qkvz``, ``W_ba``, the convolution's taps,
    ``A_log`` and ``dt_bias``, ``W_out`` (the gated norm's scale is not
    counted, as no norm is)."""
    d, hv = c["hidden_size"], c["linear_num_value_heads"]
    inner = hv * c["linear_value_head_dim"]
    return (d * (conv_channels(c) + inner + 2 * hv)
            + c["linear_conv_kernel_dim"] * conv_channels(c) + 2 * hv
            + inner * d)


def attention_params(c: dict) -> int:
    """A gated attention mixer: the doubled ``W_q``, ``W_k``, ``W_v``,
    ``W_o``."""
    d, dim = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * dim, c["num_key_value_heads"] * dim
    return d * (2 * q + 2 * kv) + q * d


def expert_params(c: dict) -> int:
    """One routed SwiGLU expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def moe_params_outside(c: dict) -> int:
    """An expert layer but its routed experts: the router as wide as
    published, the shared expert and its gate."""
    d = c["hidden_size"]
    return (d * c["num_experts_published"]
            + 3 * d * c["shared_expert_intermediate_size"] + d)


def params_outside_experts(c: dict) -> int:
    """Every parameter but the norms' scales and the routed experts."""
    return (gdn_layers(c) * gdn_params(c)
            + attention_layers(c) * attention_params(c)
            + c["num_hidden_layers"] * moe_params_outside(c)
            + 2 * c["vocab_size"] * c["hidden_size"])


def params(c: dict) -> int:
    """Every parameter held here but the norms' scales."""
    return params_outside_experts(c) \
        + c["num_hidden_layers"] * c["num_experts"] * expert_params(c)


def _published(c: dict) -> dict:
    """``c`` with the reduced keys at their published values."""
    return {**c, **{key[:-len("_published")]: value
                    for key, value in c.items()
                    if key.endswith("_published")}}


def published_params(c: dict) -> int:
    """The whole published model (every layer, expert and row of the
    vocabulary), but the norms' scales and the prediction module."""
    return params(_published(c))


def published_active_params(c: dict) -> int:
    """What one token multiplies of the whole model beside the head:
    everything outside the routed experts but the embedding (a row looked
    up) and the head, and ``num_experts_per_tok`` experts a layer."""
    whole = _published(c)
    return (params_outside_experts(whole)
            - 2 * whole["vocab_size"] * c["hidden_size"]
            + whole["num_hidden_layers"] * c["num_experts_per_tok"]
            * expert_params(c))


def experts_hit(c: dict, tokens: float) -> float:
    """Held experts a layer needs for a batch of ``tokens`` under uniform
    routing over the published experts."""
    miss = 1.0 - c["num_experts_per_tok"] / c["num_experts_published"]
    return c["num_experts"] * (1.0 - miss ** tokens)


def kv_bytes_per_token(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """K and V of the attention layers, a token."""
    return attention_layers(c) * 2 * c["num_key_value_heads"] \
        * c["head_dim"] * dtype_bytes


def matrix_state_bytes(c: dict) -> int:
    """One Gated DeltaNet layer's matrices of one sequence: ``Hv x K x V``
    float32."""
    return (c["linear_num_value_heads"] * c["linear_key_head_dim"]
            * c["linear_value_head_dim"] * STATE_BYTES)


def state_bytes_per_slot(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """What the Gated DeltaNet layers keep a sequence: the matrix state in
    float32 and ``linear_conv_kernel_dim - 1`` inputs of the convolution in
    the compute type."""
    tail = (c["linear_conv_kernel_dim"] - 1) * conv_channels(c) * dtype_bytes
    return gdn_layers(c) * (matrix_state_bytes(c) + tail)


def gdn_flops_per_token(c: dict) -> float:
    """One Gated DeltaNet layer, one token (module text)."""
    return 7.0 * matrix_state_bytes(c) / STATE_BYTES


def gdn_row_bytes_per_token(c: dict) -> float:
    """One Gated DeltaNet layer, one token: a key head's ``q`` and ``k``, a
    value head's ``v`` in and ``o`` out, ``g`` and ``beta``."""
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    return (2.0 * hk * c["linear_key_head_dim"]
            + 2.0 * hv * c["linear_value_head_dim"] + 2.0 * hv) * STATE_BYTES


def scan_chunk(c: dict, tokens: float) -> dict:
    """``{"flops", "bytes"}`` the scans of all Gated DeltaNet layers require
    for one chunk of ``tokens`` tokens of one sequence."""
    layers = gdn_layers(c)
    return {"flops": layers * tokens * gdn_flops_per_token(c),
            "bytes": layers * (tokens * gdn_row_bytes_per_token(c)
                               + 2.0 * matrix_state_bytes(c))}


def chunk_attention(c: dict, pairs: float, queries: float) -> dict:
    """``{"flops", "bytes"}`` the attention layers require for one prefill
    chunk of ``queries`` real queries that attend ``pairs`` (query, key)
    pairs a layer: both products of every query head, K and V of the context
    once a K/V head, the queries in and the outputs out."""
    h, dim = c["num_attention_heads"], c["head_dim"]
    layers = attention_layers(c)
    context = pairs / max(queries, 1.0) + queries / 2.0     # its last row
    return {"flops": layers * pairs * h * 4.0 * dim,
            "bytes": context * kv_bytes_per_token(c)
            + layers * queries * 2 * h * dim * DTYPE_BYTES}


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the qwen3_next family has no trainer in this system: the delta "
        "rule's scan has no backward here (ROADMAP R6)")


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None,
                      hit: float | None = None) -> float:
    """Bytes one decode iteration must move with ``slots`` sequences
    (default: the configuration's ``max_slots``) of ``live_kv_tokens`` tokens
    in all: the weights outside the experts once, the held experts hit
    (``hit``, summed over the layers; default the expectation under uniform
    routing) once, each sequence's state read and written, the attention
    layers' K/V rows of every live token."""
    slots = slots or config["max_slots"]
    if hit is None:
        hit = config["num_hidden_layers"] * experts_hit(config, slots)
    weights = params_outside_experts(config) + hit * expert_params(config)
    return (weights * weight_dtype_bytes
            + 2.0 * slots * state_bytes_per_slot(config)
            + live_kv_tokens * kv_bytes_per_token(config, kv_dtype_bytes))


def decode_kernel(config: dict, name: str, lives,
                  observed: dict | None = None) -> dict:
    """``{"flops", "bytes"}`` one decode iteration requires of kernel family
    ``name`` with one sequence of each length in ``lives`` decoding.
    ``observed`` may hold the step log's means over the traced interval,
    ``moe_experts_hit`` and ``moe_pairs`` (both summed over the layers):

    - ``gdn_step``: in every Gated DeltaNet layer each live sequence's matrix
      state read and written, its rows in and out, and the rule's operations;
    - ``paged_attn``: in every attention layer K and V of what each sequence
      attends read once, the queries in and the outputs out, and the score
      and value products of every query head;
    - ``moe_grouped``: the hit experts' three matrices read once a layer, and
      the products of the routed pairs;
    - ``decode_iter``: the whole iteration's bytes (:func:`decode_iter_bytes`
      with the true lengths and the hit experts);
    - ``gdn_chunk_scan`` and ``kv_chunk_attn``: not of a decode iteration but
      of one execution of the prefill program, whatever ``lives``: the scans
      of the *real* tokens of a chunk, ``observed``'s ``chunk_tokens`` over
      ``prefill_chunks``, and the attention of its real queries over the
      pairs they attend, ``chunk_pairs`` over ``prefill_chunks`` (the step log
      counts all three of the chunks alone — ``scan_tokens`` also counts a
      token a decoding slot —; a prompt's last chunk is part padding, and a
      pad position is no required work); a whole chunk from an empty context
      where the log has none."""
    n, live = len(lives), float(sum(lives))
    layers = config["num_hidden_layers"]
    observed = observed or {}
    hit = observed.get("moe_experts_hit", layers * experts_hit(config, n))
    if name in ("gdn_chunk_scan", "kv_chunk_attn"):
        chunks = observed.get("prefill_chunks")
        tokens = (observed["chunk_tokens"] / chunks if chunks
                  else config["prefill_chunk"])
        if name == "gdn_chunk_scan":
            return scan_chunk(config, tokens)
        pairs = (observed["chunk_pairs"] / chunks if chunks
                 else tokens * (tokens + 1) / 2.0)
        return chunk_attention(config, pairs, tokens)
    if name == "gdn_step":
        gdn = gdn_layers(config)
        return {"flops": gdn * n * gdn_flops_per_token(config),
                "bytes": gdn * n * (2.0 * matrix_state_bytes(config)
                                    + gdn_row_bytes_per_token(config))}
    if name == "paged_attn":
        h, dim = config["num_attention_heads"], config["head_dim"]
        attn = attention_layers(config)
        return {"flops": attn * live * h * 4.0 * dim,
                "bytes": live * kv_bytes_per_token(config)
                + attn * n * 2 * h * dim * DTYPE_BYTES}
    if name == "moe_grouped":
        pairs = observed.get(
            "moe_pairs", layers * n * config["num_experts_per_tok"]
            * config["num_experts"] / config["num_experts_published"])
        return {"flops": 2.0 * pairs * expert_params(config),
                "bytes": hit * expert_params(config) * DTYPE_BYTES}
    if name == "decode_iter":
        return {"flops": 0.0,
                "bytes": decode_iter_bytes(config, live, DTYPE_BYTES,
                                           slots=n, hit=hit)}
    raise KeyError(f"counts/qwen3_next.py has no decode kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The requirement of one execution of a program, for callers that know
    no lengths: ``gdn_chunk_scan`` / ``kv_chunk_attn`` of one prefill chunk
    of real tokens from an empty context; any other name at the
    configuration's nominal decode batch (``nominal_decode``)."""
    if name in ("gdn_chunk_scan", "kv_chunk_attn"):
        return decode_kernel(config, name, [])
    nominal = config["nominal_decode"]
    return decode_kernel(config, name,
                         [nominal["live_tokens"]] * nominal["slots"])
