"""Required operations and bytes of EvaByte (a byte-level decoder whose every
layer is an EVA attention layer: exact attention inside a tumbling window of
``window_size`` positions, one summary key/value a chunk of ``chunk_size`` for
everything before it), from shapes alone.

The yardstick every roofline share of a ``"counts": "evabyte"`` configuration
divides by.  Convention as in ``counts/gpt2.py``: one multiply-add is 2
FLOPs, only what the algorithm *requires* is counted.  Shapes come from the
configuration file's top level (the published keys: ``hidden_size``,
``num_attention_heads``, ``intermediate_size``, ``num_hidden_layers``,
``vocab_size``, ``num_pred_heads``, ``chunk_size``, ``window_size``; ``head_dim``
is ``hidden_size / num_attention_heads`` where the file has none).

What one decode step must move: every layer's weights once whatever the
batch, the final norm and the ``vocab_size`` head columns the served byte
reads (the embedding is a gather of a row a slot, and the head's other
``num_pred_heads - 1`` column blocks, published for multi-byte drafting, are
held and not read: :func:`unread_params`); in every layer, of each live
sequence at position ``t``, the K and V rows of its open window (``t %
window_size + 1``: the ring's resident rows) and the summary K and V of every
chunk of every closed window (``t // window_size * window_size /
chunk_size``: the summary rows it sees) — from each sequence's true length,
since a window caps a sequence and not the sum.

What the harness calls, and every other ``counts/<name>.py`` offers:
``train_flops_per_token``, ``decode_iter_bytes`` and ``step_kernel``; here
also ``decode_kernel(config, name, lives, observed)`` for reader
``trace_decode_kernel``, which knows every live sequence's length and the
step log's counters.
"""

from __future__ import annotations

DTYPE_BYTES = 2


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def layer_params(c: dict) -> int:
    """One layer: q, k, v and the output projection, ``mu`` and ``phi`` (a
    head's two pooling vectors), the SwiGLU, the two norms' offsets."""
    d, qd = c["hidden_size"], c["num_attention_heads"] * head_dim(c)
    return 4 * d * qd + 2 * qd + 3 * d * c["intermediate_size"] + 2 * d


def params(c: dict) -> int:
    """Every parameter: the layers, the embedding, the final norm and the
    untied head of ``num_pred_heads x vocab_size`` columns."""
    d, v = c["hidden_size"], c["vocab_size"]
    return (c["num_hidden_layers"] * layer_params(c) + v * d + d
            + d * c["num_pred_heads"] * v)


def unread_params(c: dict) -> int:
    """What a served step holds and does not read: the embedding (a gather of
    a row a slot) and the head's column blocks past the next byte's."""
    return c["vocab_size"] * c["hidden_size"] * c["num_pred_heads"]


def row_bytes(c: dict, dtype_bytes: int = DTYPE_BYTES) -> int:
    """A cached row a layer — a token's, or a chunk's summary: K and V of
    every head."""
    return 2 * c["num_attention_heads"] * head_dim(c) * dtype_bytes


def ring_rows(c: dict, length: int) -> int:
    """Token rows a query at position ``length - 1`` attends: its window's,
    up to itself."""
    return (length - 1) % c["window_size"] + 1


def summary_rows(c: dict, length: int) -> int:
    """Summary rows a query at position ``length - 1`` attends: one a chunk
    of every closed window."""
    w = c["window_size"]
    return (length - 1) // w * (w // c["chunk_size"])


def train_flops_per_token(config: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the evabyte family has no trainer in this system: at 16 bytes a "
        "parameter four layers (4 x 202 M) are 12.9 GB before activations, "
        "so no depth inside the guide's floors trains on one chip (ISSUE 48)")


def decode_iter_bytes(config: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int, kv_dtype_bytes: int = 2,
                      slots: int | None = None) -> float:
    """Bytes one decode step must move with ``slots`` sequences (default:
    the configuration's ``max_slots``) of ``live_kv_tokens`` tokens in all,
    each taken as the mean length (a caller that knows the lengths asks
    :func:`decode_kernel`): the weights read once, each sequence's ring rows
    and visible summary rows in every layer."""
    slots = slots or config["max_slots"]
    each = max(int(live_kv_tokens / slots), 1)
    rows = slots * (ring_rows(config, each) + summary_rows(config, each))
    return ((params(config) - unread_params(config)) * weight_dtype_bytes
            + config["num_hidden_layers"] * rows
            * row_bytes(config, kv_dtype_bytes))


def decode_kernel(config: dict, name: str, lives,
                  observed: dict | None = None) -> dict:
    """``{"flops", "bytes"}`` one execution of a program requires of kernel
    family ``name`` with one sequence of each length in ``lives`` decoding:

    - ``eva_attn`` (the decode step's two walks of ``paged_attn``): in every
      layer the ring rows and the visible summary rows of each sequence read
      once, the queries in and the outputs out, and the score and value
      products of every head (``4 D H`` a row);
    - ``decode_iter``: the whole step's bytes (the weights read once and the
      same rows);
    - ``summarise`` (decode): a sixteenth of the sequences complete a chunk
      a step: its ``chunk_size`` rows read, one summary row written, two
      pooling scores and two weighted sums a row;
    - ``eva_chunk_attn`` (a prefill chunk's two walks of ``kv_chunk_attn``):
      ``4 D H`` a score pair over the chunk's causal pairs inside its window
      (the mean over the chunk grid's offsets in a window) and over chunk x
      visible summaries — the summaries a chunk saw are the step log's
      ``chunk_summary_rows_read`` (``observed``, the mean over the traced
      interval's prefill iterations, divided by the chunks an iteration's
      budget holds); K, V and the summaries read once a head, the queries in,
      the outputs out;
    - ``chunk_summarise``: a prefill chunk's rows read, its summaries
      written."""
    n = len(lives)
    layers, c, w = (config["num_hidden_layers"], config["chunk_size"],
                    config["window_size"])
    qd = config["num_attention_heads"] * head_dim(config)
    row = row_bytes(config)
    rows = sum(ring_rows(config, x) + summary_rows(config, x) for x in lives)
    if name == "eva_attn":
        return {"flops": layers * rows * 4.0 * qd,
                "bytes": layers * (rows * row + n * 2.0 * qd * DTYPE_BYTES)}
    if name == "decode_iter":
        return {"flops": 0.0,
                "bytes": (params(config) - unread_params(config))
                * DTYPE_BYTES + layers * rows * row}
    if name == "summarise":
        return {"flops": layers * n / c * c * 8.0 * qd,
                "bytes": layers * n / c * (c + 1) * row}
    t = config["prefill_chunk"]
    if name == "eva_chunk_attn":
        per_iter = max(1, config.get("prefill_budget", t) // t)
        seen = (observed or {}).get("chunk_summary_rows_read", 0.0) / per_iter
        pairs = t * (w + 1) / 2.0 + t * seen
        return {"flops": layers * pairs * 4.0 * qd,
                "bytes": layers * ((min(t, w) + (w - t) / 2.0 + seen) * row
                                   + t * 2.0 * qd * DTYPE_BYTES)}
    if name == "chunk_summarise":
        return {"flops": layers * t * 8.0 * qd,
                "bytes": layers * (t + t / c) * row}
    raise KeyError(f"counts/evabyte.py has no kernel {name!r}")


def step_kernel(config: dict, name: str) -> dict:
    """The same requirement at the configuration's nominal decode batch
    (``nominal_decode``: ``slots`` sequences of ``live_tokens`` each, a
    prefill chunk that sees ``chunk_summary_rows`` summaries), for callers
    that know no lengths."""
    nominal = config["nominal_decode"]
    return decode_kernel(
        config, name, [nominal["live_tokens"]] * nominal["slots"],
        {"chunk_summary_rows_read": nominal.get("chunk_summary_rows", 0)})
