"""Required operations and bytes of Ling-3.0-flash-VL's language model (Kimi
Delta Attention layers, a latent-attention layer every sixth, group-limited
sigmoid experts of which a chip holds one group), from shapes alone.

The yardstick every roofline share of a ``"counts": "ling"`` configuration
divides by.  Convention as in ``counts/gpt2.py``: one multiply-add is 2
FLOPs, only what the algorithm *requires* is counted, whatever implements
it.  Shapes come from the configuration file's top level (the published
keys: ``hidden_size``, ``num_attention_heads``, ``head_dim``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``intermediate_size``, ``moe_intermediate_size``, ``num_experts`` — the
experts *held* —, ``num_experts_published``, ``num_experts_per_tok``,
``layer_types``, ``first_k_dense_replace``, ``short_conv_kernel_size``,
``vocab_size``).

**The gated delta rule**, a token a head a KDA layer, over a state of ``K x
V`` float32 values (``K = V = head_dim``): the decay of every value (1), the
prediction ``S'^T k`` (2), the rank-one correction (2) and the read ``S^T q``
(2): ``7 K V`` FLOPs, whether a step computes them on the vector unit or a
chunk of 64 as products (the chunked form spends more, on the matrix unit:
its triangular solve and its decay ratios are not required operations).
Bytes of a decode step (kernel ``kda_step``): the state read once and written
once, and a head's rows ``q, k, v, g`` in and ``o`` out with ``beta``, float32
as the recurrence computes them.  Bytes of a prefill chunk's scan (kernel
``kda_chunk_scan``): the same rows a token, the state once in and once out a
chunk.

**A decode iteration** must read every weight outside the routed experts
once whatever the batch, each held expert *that some token of the batch is
routed to* once, read *and write* the state of every live sequence
(``state_bytes_per_slot``: a step updates all of it), and read of every live
token the latent row of the MLA layers (``kv_bytes_per_token``: 1,152 bytes
at the published widths).  Which experts are hit depends on the weights; the
requirement uses the expectation under uniform routing over the published
experts unless the step log's counters are handed in.

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for reader
``trace_decode_kernel``, which knows every live sequence's length and the
step log's routing counters.
"""

from __future__ import annotations

DTYPE_BYTES = 2
STATE_BYTES = 4


def kda_layers(c: dict) -> int:
    return sum(kind == "kda" for kind in c["layer_types"])


def mla_layers(c: dict) -> int:
    return len(c["layer_types"]) - kda_layers(c)


def expert_layers(c: dict) -> int:
    return len(c["layer_types"]) - c["first_k_dense_replace"]


def channels(c: dict) -> int:
    return c["num_attention_heads"] * c["head_dim"]


def kda_params(c: dict) -> int:
    """A KDA mixer: the q, k, v projections and their taps, the decay gate
    and its bias, ``A_log``, ``W_beta``, the output gate and its bias, the
    output projection (the head norm's scale is not counted, as no norm
    is)."""
    d, ch, h = c["hidden_size"], channels(c), c["num_attention_heads"]
    return (3 * d * ch + 3 * c["short_conv_kernel_size"] * ch
            + d * ch + ch + h + d * h + d * ch + ch + ch * d)


def mla_params(c: dict) -> int:
    d, h, rank = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + v) + d * h + h * v * d)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def params_outside_experts(c: dict) -> int:
    """Every parameter but the norms' scales and the routed experts: the
    mixers, the leading dense SwiGLU, the routers (as wide as published) and
    their selection bias, the shared experts, the embedding and the head."""
    d = c["hidden_size"]
    router = d * c["num_experts_published"] + c["num_experts_published"]
    return (kda_layers(c) * kda_params(c) + mla_layers(c) * mla_params(c)
            + c["first_k_dense_replace"] * 3 * d * c["intermediate_size"]
            + expert_layers(c) * (router + expert_params(c))
            + 2 * c["vocab_size"] * d)


def params(c: dict) -> int:
    """Every parameter held here but the norms' scales."""
    return params_outside_experts(c) \
        + expert_layers(c) * c["num_experts"] * expert_params(c)


def experts_hit(c: dict, tokens: float) -> float:
    """Held experts an expert layer needs for a batch of ``tokens`` under
    uniform routing over the published experts."""
    miss = 1.0 - c["num_experts_per_tok"] / c["num_experts_published"]
    return c["num_experts"] * (1.0 - miss ** tokens)


def kv_bytes_per_token(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """The latent row ``[c_kv | k_rope]`` of the MLA layers, a token."""
    return mla_layers(c) * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        * dtype_bytes


def matrix_state_bytes(c: dict) -> int:
    """One KDA layer's matrices of one sequence: ``H x K x V`` float32."""
    return channels(c) * c["head_dim"] * STATE_BYTES


def state_bytes_per_slot(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """What the KDA layers keep a sequence: the matrix state in float32 and
    ``short_conv_kernel_size - 1`` inputs of each of the three convolutions
    in the compute type."""
    tails = 3 * (c["short_conv_kernel_size"] - 1) * channels(c) * dtype_bytes
    return kda_layers(c) * (matrix_state_bytes(c) + tails)


def delta_flops_per_token(c: dict) -> float:
    """One KDA layer, one token (module text)."""
    return 7.0 * channels(c) * c["head_dim"]


def delta_row_bytes_per_token(c: dict) -> float:
    """One KDA layer, one token: ``q, k, v, g`` in, ``o`` out, ``beta``."""
    return (5.0 * channels(c) + c["num_attention_heads"]) * STATE_BYTES


def scan_chunk(c: dict, tokens: int) -> dict:
    """``{"flops", "bytes"}`` the scans of all KDA layers require for one
    chunk of ``tokens`` tokens of one sequence."""
    layers = kda_layers(c)
    return {"flops": layers * tokens * delta_flops_per_token(c),
            "bytes": layers * (tokens * delta_row_bytes_per_token(c)
                               + 2.0 * matrix_state_bytes(c))}


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the ling family has no trainer in this system: the gated delta rule "
        "has no backward here, and at 16 bytes a parameter one expert "
        "layer's 64-expert share (384 M parameters) is 6.1 GB (ISSUE 52)")


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None,
                      hit: float | None = None) -> float:
    """Bytes one decode iteration must move with ``slots`` sequences
    (default: the configuration's ``max_slots``) of ``live_kv_tokens`` tokens
    in all: the weights outside the experts once, the held experts hit
    (``hit``, summed over the expert layers; default the expectation under
    uniform routing) once, each sequence's state read and written, the MLA
    layers' latent row of every live token."""
    slots = slots or config["max_slots"]
    if hit is None:
        hit = expert_layers(config) * experts_hit(config, slots)
    weights = params_outside_experts(config) + hit * expert_params(config)
    return (weights * weight_dtype_bytes
            + 2.0 * slots * state_bytes_per_slot(config)
            + live_kv_tokens * kv_bytes_per_token(config, kv_dtype_bytes))


#: the name ISSUE 52 gives the same requirement
decode_step_bytes = decode_iter_bytes


def decode_kernel(config: dict, name: str, lives,
                  observed: dict | None = None) -> dict:
    """``{"flops", "bytes"}`` one decode iteration requires of kernel family
    ``name`` with one sequence of each length in ``lives`` decoding.
    ``observed`` may hold the step log's means over the traced interval,
    ``moe_experts_hit`` and ``moe_pairs`` (both summed over the expert
    layers):

    - ``kda_step``: in every KDA layer each live sequence's matrix state read
      and written, its rows in and out, and the delta rule's operations;
    - ``paged_latent_attn``: in every MLA layer the latent row of what each
      sequence attends read once, the absorbed queries in and the latent
      outputs out, and the score and value products of every head over the
      latent widths;
    - ``moe_grouped``: the hit experts' three matrices read once an expert
      layer, and the products of the routed pairs;
    - ``decode_iter``: the whole iteration's bytes
      (:func:`decode_iter_bytes` with the true lengths and the hit
      experts);
    - ``kda_chunk_scan``: not of a decode iteration but of one execution of
      the prefill program, whatever ``lives``: the scans of the *real* tokens
      of a chunk, ``observed``'s ``scan_tokens`` over ``prefill_chunks`` (the
      step log counts both; a prompt's last chunk is part padding, and an
      identity step is no required work); the chunk's width where the log
      has neither."""
    n, live = len(lives), float(sum(lives))
    layers = expert_layers(config)
    observed = observed or {}
    hit = observed.get("moe_experts_hit", layers * experts_hit(config, n))
    if name == "kda_chunk_scan":
        chunks = observed.get("prefill_chunks")
        return scan_chunk(config, observed["scan_tokens"] / chunks
                          if chunks else config["prefill_chunk"])
    if name == "kda_step":
        kda = kda_layers(config)
        return {"flops": kda * n * delta_flops_per_token(config),
                "bytes": kda * n * (2.0 * matrix_state_bytes(config)
                                    + delta_row_bytes_per_token(config))}
    if name == "paged_latent_attn":
        h, rank = config["num_attention_heads"], config["kv_lora_rank"]
        rope = config["qk_rope_head_dim"]
        mla = mla_layers(config)
        return {"flops": mla * live * h * 2.0 * (2 * rank + rope),
                "bytes": live * kv_bytes_per_token(config)
                + mla * n * h * (2 * rank + rope) * DTYPE_BYTES}
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
    raise KeyError(f"counts/ling.py has no decode kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The requirement of one execution of a program, for callers that know
    no lengths: ``kda_chunk_scan`` of one prefill chunk of real tokens (the
    most a chunk requires; ``kda_scan_roofline_pct`` takes the real tokens
    from the step log through :func:`decode_kernel`); any other name at the
    configuration's nominal decode batch (``nominal_decode``)."""
    if name == "kda_chunk_scan":
        return scan_chunk(config, config["prefill_chunk"])
    nominal = config["nominal_decode"]
    return decode_kernel(config, name,
                         [nominal["live_tokens"]] * nominal["slots"])
