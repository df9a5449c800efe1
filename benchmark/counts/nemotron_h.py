"""Required operations and bytes of NVIDIA-Nemotron-3-Super-120B-A12B's
language model (``nemotron_h``: Mamba-2 layers, latent expert layers of which a
chip holds a quarter, attention layers of grouped queries), from shapes alone.

The yardstick every roofline share of a ``"counts": "nemotron_h"``
configuration divides by.  Convention as in ``counts/gpt2.py``: one
multiply-add is 2 FLOPs, only what the algorithm *requires* is counted,
whatever implements it.  Shapes come from the configuration file's top level
(the published keys: ``hidden_size``, ``hybrid_override_pattern``,
``mamba_num_heads``, ``mamba_head_dim``, ``n_groups``, ``ssm_state_size``,
``conv_kernel``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``moe_intermediate_size``, ``moe_latent_size``,
``moe_shared_expert_intermediate_size``, ``n_routed_experts`` — the experts
*held* —, ``n_routed_experts_published``, ``num_experts_per_tok``,
``vocab_size``).

**Mamba-2's recurrence**, a token a head an ``M`` layer, over a state of ``P x
N`` float32 values (``P = mamba_head_dim``, ``N = ssm_state_size``): the decay
of every value (1), the rank-one update ``dt x (outer) B`` (2) and the read ``S
C`` (2): ``5 P N`` FLOPs, whether a step computes them on the vector unit or a
chunk of 128 as products (the chunked form spends more, on the matrix unit:
its ``C B^T`` and its decay ratios are not required operations).  Bytes of a
decode step (scope ``mamba2/step``): the state read once and written once, and
a head's ``x`` in and ``y`` out with ``dt``, a group's ``B`` and ``C``,
float32 as the recurrence computes them.  Bytes of a prefill chunk's scan
(scope ``mamba2/scan``): the same rows a token, the state once in and once out
a chunk.

**A decode iteration** must read every weight outside the routed experts once
whatever the batch, each held expert *that some token of the batch is routed
to* once, read *and write* the state of every live sequence
(``state_bytes_per_slot``: a step updates all of it), and read of every live
token the K/V rows of the attention layers (``kv_bytes_per_token``: 1,024
bytes at the published widths).  Which experts are hit depends on the weights;
the requirement uses the expectation under uniform routing over the published
experts unless the step log's counters are handed in.

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for reader
``trace_decode_kernel``, which knows every live sequence's length and the
step log's routing counters, and ``published_params`` /
``published_active_params``: the whole model's count from the published keys
(120.67 B, 12.2 B a token), which ``tests/test_nemotron_h.py`` holds to the
name "120B-A12B".
"""

from __future__ import annotations

DTYPE_BYTES = 2
STATE_BYTES = 4


def layers_of(c: dict, kind: str) -> int:
    return c["hybrid_override_pattern"].count(kind)


def d_inner(c: dict) -> int:
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_channels(c: dict) -> int:
    return d_inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def mamba_params(c: dict) -> int:
    """A Mamba-2 mixer: ``W_in`` (``z | x | B | C | dt``), the convolution's
    taps and bias, ``dt_bias``, ``A_log``, ``D``, ``W_out`` (the gated norm's
    scale is not counted, as no norm is)."""
    d, h = c["hidden_size"], c["mamba_num_heads"]
    return (d * (d_inner(c) + conv_channels(c) + h)
            + (c["conv_kernel"] + 1) * conv_channels(c) + 3 * h
            + d_inner(c) * d)


def attention_params(c: dict) -> int:
    d, dim = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * dim, c["num_key_value_heads"] * dim
    return d * (q + 2 * kv) + q * d


def expert_params(c: dict) -> int:
    """One routed expert: two matrices in the latent, no gate."""
    return 2 * c["moe_latent_size"] * c["moe_intermediate_size"]


def expert_layer_params_outside(c: dict) -> int:
    """An expert layer but its routed experts: the router as wide as
    published and its selection bias, the two latent projections, the shared
    expert on the token's own width."""
    d, e = c["hidden_size"], c["n_routed_experts_published"]
    return (d * e + e + 2 * d * c["moe_latent_size"]
            + 2 * d * c["moe_shared_expert_intermediate_size"])


def params_outside_experts(c: dict) -> int:
    """Every parameter but the norms' scales and the routed experts."""
    return (layers_of(c, "M") * mamba_params(c)
            + layers_of(c, "*") * attention_params(c)
            + layers_of(c, "E") * expert_layer_params_outside(c)
            + 2 * c["vocab_size"] * c["hidden_size"])


def params(c: dict) -> int:
    """Every parameter held here but the norms' scales."""
    return params_outside_experts(c) \
        + layers_of(c, "E") * c["n_routed_experts"] * expert_params(c)


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
    """What one token multiplies of the whole model: everything outside the
    routed experts but the embedding (a row looked up, not multiplied), and
    ``num_experts_per_tok`` experts an expert layer."""
    whole = _published(c)
    return (params_outside_experts(whole)
            - whole["vocab_size"] * c["hidden_size"]
            + layers_of(whole, "E") * c["num_experts_per_tok"]
            * expert_params(c))


def experts_hit(c: dict, tokens: float) -> float:
    """Held experts an expert layer needs for a batch of ``tokens`` under
    uniform routing over the published experts."""
    miss = 1.0 - c["num_experts_per_tok"] / c["n_routed_experts_published"]
    return c["n_routed_experts"] * (1.0 - miss ** tokens)


def kv_bytes_per_token(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """K and V of the attention layers, a token."""
    return layers_of(c, "*") * 2 * c["num_key_value_heads"] * c["head_dim"] \
        * dtype_bytes


def matrix_state_bytes(c: dict) -> int:
    """One Mamba-2 layer's matrices of one sequence: ``H x P x N`` float32."""
    return d_inner(c) * c["ssm_state_size"] * STATE_BYTES


def state_bytes_per_slot(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """What the Mamba-2 layers keep a sequence: the matrix state in float32
    and ``conv_kernel - 1`` inputs of the convolution in the compute type."""
    tail = (c["conv_kernel"] - 1) * conv_channels(c) * dtype_bytes
    return layers_of(c, "M") * (matrix_state_bytes(c) + tail)


def ssd_flops_per_token(c: dict) -> float:
    """One Mamba-2 layer, one token (module text)."""
    return 5.0 * d_inner(c) * c["ssm_state_size"]


def ssd_row_bytes_per_token(c: dict) -> float:
    """One Mamba-2 layer, one token: ``x`` in, ``y`` out, ``dt``, ``B``,
    ``C``."""
    return (2.0 * d_inner(c) + c["mamba_num_heads"]
            + 2.0 * c["n_groups"] * c["ssm_state_size"]) * STATE_BYTES


def scan_chunk(c: dict, tokens: float) -> dict:
    """``{"flops", "bytes"}`` the scans of all Mamba-2 layers require for one
    chunk of ``tokens`` tokens of one sequence."""
    layers = layers_of(c, "M")
    return {"flops": layers * tokens * ssd_flops_per_token(c),
            "bytes": layers * (tokens * ssd_row_bytes_per_token(c)
                               + 2.0 * matrix_state_bytes(c))}


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the nemotron_h family has no trainer in this system: the scan has "
        "no backward here (ROADMAP R6), and at 16 bytes a parameter the "
        "guide's floors themselves (8 experts a layer, an eighth of the "
        "vocabulary, 11 layers) are 19.4 GB (ISSUE 54)")


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None,
                      hit: float | None = None) -> float:
    """Bytes one decode iteration must move with ``slots`` sequences
    (default: the configuration's ``max_slots``) of ``live_kv_tokens`` tokens
    in all: the weights outside the experts once, the held experts hit
    (``hit``, summed over the expert layers; default the expectation under
    uniform routing) once, each sequence's state read and written, the
    attention layers' K/V rows of every live token."""
    slots = slots or config["max_slots"]
    if hit is None:
        hit = layers_of(config, "E") * experts_hit(config, slots)
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
    layers):

    - ``ssd_step``: in every Mamba-2 layer each live sequence's matrix state
      read and written, its rows in and out, and the recurrence's operations;
    - ``paged_attn``: in every attention layer K and V of what each sequence
      attends read once, the queries in and the outputs out, and the score
      and value products of every query head;
    - ``moe_grouped``: the hit experts' two matrices read once an expert
      layer, and the products of the routed pairs;
    - ``decode_iter``: the whole iteration's bytes (:func:`decode_iter_bytes`
      with the true lengths and the hit experts);
    - ``ssd_chunk_scan``: not of a decode iteration but of one execution of
      the prefill program, whatever ``lives``: the scans of the *real* tokens
      of a chunk, ``observed``'s ``scan_tokens`` over ``prefill_chunks`` (the
      step log counts both; a prompt's last chunk is part padding, and an
      identity step is no required work); the chunk's width where the log
      has neither."""
    n, live = len(lives), float(sum(lives))
    layers = layers_of(config, "E")
    observed = observed or {}
    hit = observed.get("moe_experts_hit", layers * experts_hit(config, n))
    if name == "ssd_chunk_scan":
        chunks = observed.get("prefill_chunks")
        return scan_chunk(config, observed["scan_tokens"] / chunks
                          if chunks else config["prefill_chunk"])
    if name == "ssd_step":
        mamba = layers_of(config, "M")
        return {"flops": mamba * n * ssd_flops_per_token(config),
                "bytes": mamba * n * (2.0 * matrix_state_bytes(config)
                                      + ssd_row_bytes_per_token(config))}
    if name == "paged_attn":
        h, dim = config["num_attention_heads"], config["head_dim"]
        attn = layers_of(config, "*")
        return {"flops": attn * live * h * 4.0 * dim,
                "bytes": live * kv_bytes_per_token(config)
                + attn * n * 2 * h * dim * DTYPE_BYTES}
    if name == "moe_grouped":
        pairs = observed.get(
            "moe_pairs", layers * n * config["num_experts_per_tok"]
            * config["n_routed_experts"]
            / config["n_routed_experts_published"])
        return {"flops": 2.0 * pairs * expert_params(config),
                "bytes": hit * expert_params(config) * DTYPE_BYTES}
    if name == "decode_iter":
        return {"flops": 0.0,
                "bytes": decode_iter_bytes(config, live, DTYPE_BYTES,
                                           slots=n, hit=hit)}
    raise KeyError(f"counts/nemotron_h.py has no decode kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The requirement of one execution of a program, for callers that know
    no lengths: ``ssd_chunk_scan`` of one prefill chunk of real tokens (the
    most a chunk requires; ``ssd_scan_roofline_pct`` takes the real tokens
    from the step log through :func:`decode_kernel`); any other name at the
    configuration's nominal decode batch (``nominal_decode``)."""
    if name == "ssd_chunk_scan":
        return scan_chunk(config, config["prefill_chunk"])
    nominal = config["nominal_decode"]
    return decode_kernel(config, name,
                         [nominal["live_tokens"]] * nominal["slots"])
