"""Serving engine: continuous-batching generation server (ISSUE 6 + 14 + 15).

The online half of the stack: ``kv_cache`` (paged block-pool KV with a
refcounted copy-on-write allocator + prefix index), ``model`` (the
compiled serving programs of a family of layer functions — chunked prefill
that reads a slot's earlier chunks from the pool, paged one-token decode,
and the fused decode/verify fast path), ``sampling`` (the one logits→probs
reference + the fused/rejection sampler), ``draft`` (model-free n-gram
draft proposals for self-speculative decoding), ``engine`` (thread-safe
queue + continuous batching scheduler with decode-integrated budgeted
prefill + SLO metrics), ``server`` (``/generatez`` HTTP frontend —
blocking or chunked-streaming — on the obs StatusServer pattern).  Entry
point: ``serve.py`` at the repo root.
"""

from .engine import Engine, GenRequest, QueueFullError  # noqa: F401
from .kv_cache import BlockAllocator, OutOfBlocksError, PagedKVCache  # noqa: F401
from .model import (  # noqa: F401
    make_decode_fn,
    make_fused_decode_fn,
    make_prefill_fn,
    make_programs,
)
from .server import ServeServer  # noqa: F401
