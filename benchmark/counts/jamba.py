"""Required operations and bytes of the AI21 Jamba decoder (Mamba-1 layers,
an attention layer every ``attn_layer_period``, a dense SwiGLU in every
layer, the embedding tied to the head), from shapes alone.

The yardstick every roofline share of a ``"counts": "jamba"`` configuration
divides by.  Convention as in ``counts/gpt2.py``: one multiply-add is 2
FLOPs, only what the algorithm *requires* is counted.  Shapes come from the
configuration file's top level (the published keys: ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``num_hidden_layers``, ``attn_layer_period``, ``attn_layer_offset``,
``mamba_expand``, ``mamba_d_state``, ``mamba_dt_rank``, ``mamba_d_conv``,
``vocab_size``).

**The selective scan** (kernel ``ssm_chunk_scan``), a token a Mamba layer,
over ``C = mamba_expand * hidden_size`` channels and ``N = mamba_d_state``
states.  Operations, a (channel, state) pair: the product ``delta * A`` (1),
its exponential (1), the product ``(delta * u') * B`` (1; ``delta * u'``
itself is 1 a channel), the multiply-add into the state (2), the product
with ``C`` and its sum over the states (2): ``7 * C * N + C`` a token, and
``2 * C`` more for ``D * u'`` and its addition.  Bytes: ``u'``, ``delta``,
``B``, ``C`` read once and ``y`` written once a token, in the compute type
(``3 * C + 2 * N`` values), and the state (``N * C`` float32) read once and
written once a chunk.  The gate ``z`` is applied outside the kernel and is
not counted.  No part of this is a matrix product: the kernel runs on the
vector unit, and the published compute peak it is divided by is the matrix
unit's, so its share reads low by construction (PERF.md section 3).

**A decode iteration** must read every weight once whatever the batch (the
tied embedding once: the head reads all of it), read *and write* the state
of every live slot (``state_bytes_per_slot``: a step updates all of it), and
read of every live token the K and V of the attention layers
(``kv_bytes_per_token``: 1,024 bytes at the published widths).

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for reader
``trace_decode_kernel``, which knows every live sequence's length.
"""

from __future__ import annotations

DTYPE_BYTES = 2
STATE_BYTES = 4


def channels(c: dict) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def attention_layers(c: dict) -> int:
    return sum(i % c["attn_layer_period"] == c["attn_layer_offset"]
               for i in range(c["num_hidden_layers"]))


def mamba_layers(c: dict) -> int:
    return c["num_hidden_layers"] - attention_layers(c)


def mamba_params(c: dict) -> int:
    """The mixer of a Mamba layer: ``in_proj``, the convolution and its bias,
    ``x_proj``, ``dt_proj`` and its bias, ``A_log``, ``D``, ``out_proj`` (the
    three norms of ``R + 2 N`` scales are not counted, as no norm is)."""
    d, ch = c["hidden_size"], channels(c)
    n, r = c["mamba_d_state"], c["mamba_dt_rank"]
    return (d * 2 * ch + ch * c["mamba_d_conv"] + ch + ch * (r + 2 * n)
            + r * ch + ch + ch * n + ch + ch * d)


def attention_params(c: dict) -> int:
    d, h, kv = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    dim = d // h
    return d * h * dim + 2 * d * kv * dim + h * dim * d


def ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def params(c: dict) -> int:
    """Every parameter but the norms' scales; the tied embedding once."""
    return (mamba_layers(c) * mamba_params(c)
            + attention_layers(c) * attention_params(c)
            + c["num_hidden_layers"] * ffn_params(c)
            + c["vocab_size"] * c["hidden_size"])


def state_bytes_per_slot(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """What the Mamba layers keep a sequence: the scan state in float32 and
    ``mamba_d_conv - 1`` inputs of the convolution in the compute type."""
    ch = channels(c)
    return mamba_layers(c) * (ch * c["mamba_d_state"] * STATE_BYTES
                              + (c["mamba_d_conv"] - 1) * ch * dtype_bytes)


def kv_bytes_per_token(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """K and V of the attention layers, a token."""
    dim = c["hidden_size"] // c["num_attention_heads"]
    return attention_layers(c) * 2 * c["num_key_value_heads"] * dim \
        * dtype_bytes


def scan_flops_per_token(c: dict) -> float:
    """One Mamba layer, one token (module text)."""
    ch, n = channels(c), c["mamba_d_state"]
    return 7.0 * ch * n + ch + 2.0 * ch


def scan_bytes_per_token(c: dict, dtype_bytes: int = DTYPE_BYTES) -> float:
    """One Mamba layer, one token: ``u'``, ``delta``, ``y`` and ``B``, ``C``."""
    return (3.0 * channels(c) + 2.0 * c["mamba_d_state"]) * dtype_bytes


def scan_chunk(c: dict, tokens: int) -> dict:
    """``{"flops", "bytes"}`` the scans of all Mamba layers require for one
    chunk of ``tokens`` tokens of one sequence: the tokens' terms, and each
    layer's state read once and written once."""
    layers = mamba_layers(c)
    state = 2.0 * channels(c) * c["mamba_d_state"] * STATE_BYTES
    return {"flops": layers * tokens * scan_flops_per_token(c),
            "bytes": layers * (tokens * scan_bytes_per_token(c) + state)}


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the jamba family has no trainer in this system: the selective scan "
        "has no backward here, and at 16 bytes a parameter one period of the "
        "layer pattern is 23 GB (ISSUE 34)")


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None) -> float:
    """Bytes one decode iteration must move with ``slots`` sequences
    (default: the configuration's ``max_slots``) of ``live_kv_tokens``
    tokens in all: the weights once, each sequence's state read and
    written, the attention layers' K and V of every live token."""
    slots = slots or config["max_slots"]
    return (params(config) * weight_dtype_bytes
            + 2.0 * slots * state_bytes_per_slot(config)
            + live_kv_tokens * kv_bytes_per_token(config, kv_dtype_bytes))


def decode_kernel(config: dict, name: str, lives,
                  observed: dict | None = None) -> dict:
    """``{"flops", "bytes"}`` one decode iteration requires of kernel family
    ``name`` with one sequence of each length in ``lives`` decoding:

    - ``paged_attn``: in every attention layer, K and V of what each sequence
      attends read once, the queries in and the outputs out, and the score
      and value products of every query head;
    - ``decode_iter``: the whole iteration's bytes
      (:func:`decode_iter_bytes` with the true lengths)."""
    n, live = len(lives), float(sum(lives))
    if name == "paged_attn":
        d, h = config["hidden_size"], config["num_attention_heads"]
        dim = d // h
        layers = attention_layers(config)
        return {"flops": layers * live * h * 4.0 * dim,
                "bytes": live * kv_bytes_per_token(config)
                + layers * n * 2.0 * h * dim * DTYPE_BYTES}
    if name == "decode_iter":
        return {"flops": 0.0,
                "bytes": decode_iter_bytes(config, live, DTYPE_BYTES,
                                           slots=n)}
    raise KeyError(f"counts/jamba.py has no decode kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The requirement of one execution of a program, for callers that know
    no lengths: ``ssm_chunk_scan`` of one prefill chunk (the program scans
    ``prefill_chunk`` positions in every Mamba layer whatever part of them is
    padding, an identity step costing what a real one does: the requirement
    counts the chunk's width, which overstates it by the padded share of a
    prompt's last chunk, ~5 % of the cell's positions); any other name at
    the configuration's nominal decode batch (``nominal_decode``)."""
    if name == "ssm_chunk_scan":
        return scan_chunk(config, config["prefill_chunk"])
    nominal = config["nominal_decode"]
    return decode_kernel(config, name,
                         [nominal["live_tokens"]] * nominal["slots"])
