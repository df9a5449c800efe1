"""Continuous-batching generation engine: queue → slots → paged decode.

The dense-cache reference (``models.generate``, what this engine's tests
hold its tokens to) decodes a whole batch in one ``lax.scan``: every
sequence pays ``max_new_tokens`` steps, a finished sequence squats its slot
emitting EOS, and nothing can join mid-flight — fine for offline eval and
as a reference, fatal for request serving.  This engine is what serves:

- **thread-safe FIFO queue** (bounded; a full queue rejects loudly so the
  frontend can return 429 instead of letting latency grow unboundedly);
- **continuous (in-flight) batching with decode-integrated chunked
  prefill**: every scheduler iteration first admits queued requests into
  free slots, then runs at most ``prefill_budget`` TOKENS of prefill
  chunks — budget-bounded bursts rotating round-robin across the
  admitted-but-unfilled requests (a first token waits for its prompt's
  last chunk, so a burst goes to one filler; rotation keeps prefill fair
  across fillers) —
  and then ONE paged decode step for all decoding slots, then evicts
  finished sequences (EOS / max_new_tokens).  Decode never starves: a
  newly arrived long prompt can delay the running requests' next token
  by at most one budget's worth of chunks per iteration (instead of its
  whole prefill), and queued requests' time-to-first-token overlaps with
  in-flight decode.  A request's first token is sampled in the iteration
  its last chunk completes (TTFT stops there).  ``prefill_budget=None``
  = unbudgeted (all pending chunks run before each decode step).  Under
  a budget the chunks of iteration n + 1 are launched in iteration n,
  behind its decode step and before its tokens are fetched: the device
  has the chunk to run while the host fetches, commits, logs and admits,
  and iteration n + 1 collects it (a first token, the counts) where it
  would have launched — the same programs in the same order;
- **paged KV with prefix caching** (``serve.kv_cache``): admission
  reserves only the request's worst-case footprint (prompt + max_new),
  not ``max_seq`` — and with ``prefix_cache=True``, whole token-aligned
  blocks matching an indexed prefix (system prompts, few-shot headers)
  are mapped in shared at refcount+1, so the reservation shrinks to the
  footprint MINUS the mapped prefix and prefill skips the cached tokens.
  Completed prompts register their full blocks; release decrements
  refcounts (registered blocks stay warm, LRU-evicted only under
  pressure, never while mapped);
- **admission control**: a request is admitted only when a slot AND its
  whole block reservation are free (no mid-flight OOM), strictly in
  arrival order (head-of-line blocking keeps FIFO fairness — a small
  request never jumps a large one under backpressure);
- **one host pass a decode iteration**: the one-token program hands back
  the arg-max of its logits, so an iteration in which nobody samples
  fetches a token a slot (``slots x 4`` bytes) and leaves the logits on
  the device; a request with ``temperature > 0`` makes that iteration
  fetch them and takes the numpy sampler on its row.  The batch then
  commits in one pass — counters and histograms once an iteration, the
  requests' own fields in one loop — and every stream's line is handed
  over at the end of it, in one stretch;
- **decode fast path** (ISSUE 15): with ``fused_sampling=True`` sampling
  itself moves onto the device — greedy / temperature+top-k
  sampling is folded INTO the compiled decode program
  (``serve.model.make_fused_decode_fn`` + ``serve.sampling``): per-slot
  PRNG keys and the last sampled tokens stay resident on device across
  steps, and the host fetches only the small ``(tokens, counts)`` pair
  per iteration for EOS/logging — one device dispatch per token instead
  of dispatch → logits fetch → numpy softmax → token feed-back.  With
  ``speculate=K`` on top, a model-free n-gram drafter (``serve.draft``)
  proposes up to K continuation tokens from each request's own history,
  verified in ONE multi-token paged attention pass and accepted by
  rejection sampling — greedy output stays token-for-token identical to
  the sequential path, seeded sampling stays exactly the target model's
  distribution, and an accepted burst emits up to K+1 tokens per
  dispatch.  Iterations where no slot has a draft fall back to the
  one-token fused program, so a low-hit-rate workload pays only the
  (microsecond) lookup;
- **streaming**: the newly committed tokens of the requests submitted
  with ``stream=True`` go to :attr:`Engine.stream_sink` in ONE call an
  iteration, whatever the number of streams — ``(request, tokens,
  stamp)`` triples, ``tokens`` None for a request that ended (the HTTP
  frontend's one writer thread, ``serve.server.StreamWriter``, turns
  them into the chunked ``/generatez`` transfer) — requests.jsonl rows
  are unchanged.

Observability (wired into the obs registry): ``serve_ttft_seconds``,
``serve_tpot_seconds``, ``serve_e2e_seconds``, ``serve_batch_occupancy``,
``serve_stream_lag_seconds`` histograms, queue/slot/block gauges,
``serve_requests_total{status=}`` /
``serve_tokens_generated_total`` / ``serve_admits_total{reused=}``
counters; prefix-caching counters ``serve_prefix_hits_total`` /
``serve_prefix_cached_tokens_total`` / ``serve_prefill_tokens_total`` /
``serve_prefix_evictions_total`` / ``serve_kv_cow_copies_total`` and
gauges ``serve_kv_blocks_cached`` / ``serve_kv_block_refs`` /
``serve_kv_fragmentation`` / ``serve_prefix_cache_occupancy`` /
``serve_prefix_hit_rate``; speculation counters
``serve_spec_drafted_total`` / ``serve_spec_accepted_total`` and the
``serve_decode_tokens_per_step`` histogram; a per-request
``requests.jsonl`` log (ok rows carry ``cached_prefix_tokens`` +
``prefill_tokens``, summing to ``prompt_tokens``, the per-request
``spec_drafted`` / ``spec_accepted`` draft split, and the EXCLUSIVE
tail-latency attribution ``attr_queue_s`` / ``attr_prefill_s`` /
``attr_stall_s`` / ``attr_decode_s`` / ``attr_spec_s`` / ``attr_gap_s``
summing to ``e2e_s``) and periodic ``metrics.jsonl`` rows +
``metrics.prom`` snapshots in ``logdir`` (the same streams
``tools/run_report.py`` and ``tools/check_metrics_schema.py`` consume).
Every scheduler iteration that did work additionally leaves one step-log
record — phase mix, occupancy, token/draft deltas, admissions/evictions,
prefill chunks + budget stalls, and the iteration's wall down to its
leaves, read off its ``engine.*`` span tree (mirrored into any open
profiler trace; the leaves tile the iteration and the records tile the
engine thread's life), with the thread's CPU clock beside the wall
(``offcpu_s``: it had work and did not run), its seconds inside the
collector and the stream writer's lines and lag
(docs/OBSERVABILITY.md has the vocabulary) — in a
bounded ring (``GET /stepz`` via the
frontend; :meth:`Engine.step_records`) and ``steps.jsonl``.

Threading model: HTTP/handler threads only touch :meth:`submit` (queue +
lock); all device work and all ``PagedKVCache`` mutation happens on the
single engine loop thread.  Completion is signalled per-request via a
``threading.Event``.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import itertools
import json
import math
import os
import re
import statistics
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import registry as obs_registry
from ..obs import tracing as obs_tracing
from ..ops.attention import (PAGED_LATENT_STRETCH, PAGED_STRETCH,
                             select_walk)
from ..utils.metrics import json_sanitize
from . import draft as spec_draft
from . import sampling
from .kv_cache import make_grouped_cache
from .model import make_programs

__all__ = ["Engine", "GenRequest", "QueueFullError"]

#: Terminal request states (the ``requests.jsonl`` ``status`` field).
TERMINAL_STATES = ("ok", "rejected", "error")

#: Tenant identities are identifier-style so they stay greppable in every
#: stream that carries one.
TENANT_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")
DEFAULT_TENANT = "default"


def validate_tenant(tenant) -> str:
    """Normalize + validate a tenant identity: ``None``/empty defaults to
    :data:`DEFAULT_TENANT`; anything else must match :data:`TENANT_RE`
    (raises ``ValueError`` — the serving frontend maps it to 400)."""
    if tenant is None or tenant == "":
        return DEFAULT_TENANT
    tenant = str(tenant)
    if not TENANT_RE.match(tenant):
        raise ValueError(
            f"tenant must match {TENANT_RE.pattern} "
            f"(identifier-style, <= 64 chars), got {tenant!r}"
        )
    return tenant

#: An iteration is an ``engine_stall`` (one anomaly row in ``trace.jsonl``)
#: when ``step_s + log_prev_s`` passes both: this many seconds, and this
#: many times the running median of the last ``STALL_HISTORY`` working
#: iterations (``STALL_MIN_HISTORY`` of them at least: the first
#: iterations compile).
STALL_MIN_S = 0.25
STALL_FACTOR = 20.0
STALL_HISTORY = 256
STALL_MIN_HISTORY = 8

#: Seconds inside the collector, a running total by the id of each thread
#: that has run :meth:`Engine.step`: the step record's ``gc_s`` is its
#: growth since the previous record.  Collections are not concurrent, so
#: one start stamp serves.
_gc_seconds: dict[int, float] = {}
_gc_t0 = 0.0


#: ``json.dumps`` that raises on a non-finite float (one encoder, built once)
_encode_finite = json.JSONEncoder(allow_nan=False).encode


def _gc_callback(phase: str, info: dict) -> None:
    global _gc_t0
    tid = threading.get_ident()
    if tid in _gc_seconds:
        if phase == "start":
            _gc_t0 = time.perf_counter()
        else:
            _gc_seconds[tid] += time.perf_counter() - _gc_t0


class QueueFullError(RuntimeError):
    """Raised by :meth:`Engine.submit` when the bounded queue is full
    (HTTP frontends map it to 429)."""


# eq=False: requests are live objects, not value types — membership tests
# on the _filling deque need identity, and field-wise eq would compare
# numpy fill buffers (ambiguous truth value).
@dataclasses.dataclass(eq=False)
class GenRequest:
    """One generation request plus its lifecycle bookkeeping."""

    id: str
    prompt: list[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    eos_token_id: int | None = None
    seed: int = 0
    #: Distributed-tracing id (client-supplied or generated at submit):
    #: the queue/prefill/decode spans the engine emits into trace.jsonl
    #: carry it, so a slow request's time is attributable end to end.
    trace_id: str = ""
    #: Validated tenant identity (:func:`validate_tenant`): every
    #: requests.jsonl row and step-log admission is keyed by it.
    tenant: str = DEFAULT_TENANT
    #: Absolute wall deadline (0 = none): a request still QUEUED past it
    #: is abandoned at admission instead of decoded for a client that
    #: already stopped listening (net-layer deadline honored end to end).
    t_deadline: float = 0.0
    deadline_exceeded: bool = False

    # -- lifecycle (engine-owned) --
    status: str = "queued"          # queued/active/ok/rejected/error
    finish_reason: str | None = None  # "eos" | "length"
    error: str | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    slot: int | None = None
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    occ_sum: int = 0
    occ_steps: int = 0
    occ_max: int = 0
    #: prompt tokens mapped from the prefix cache at admission (whole
    #: shared blocks) vs. prompt tokens owed to prefill compute — the two
    #: always sum to ``len(prompt)``.
    cached_prefix_tokens: int = 0
    prefill_tokens: int = 0
    #: worst observed inter-token latency (decode stall ceiling — the
    #: number the prefill budget bounds).
    itl_max_s: float = 0.0
    #: speculative-decoding accounting: draft tokens proposed for this
    #: request and how many the verifier accepted (``accepted <=
    #: drafted`` always; both 0 without ``--speculate``).
    drafted: int = 0
    accepted: int = 0
    #: tail-latency attribution: the request's e2e decomposed into
    #: EXCLUSIVE wall components charged on the engine thread — own
    #: prefill compute, interference stall (the engine was running other
    #: requests' prefill while this one was runnable), decode-program
    #: wall (non-speculative / speculative dispatches split), and
    #: scheduler gap (admit scans, bookkeeping, idle waits).  Together
    #: with queue wait (``t_admit - t_submit``) they sum to ``e2e_s`` up
    #: to clock rounding; ``_t_attr`` is the charging frontier.
    attr_prefill_s: float = 0.0
    attr_stall_s: float = 0.0
    attr_decode_s: float = 0.0
    attr_spec_s: float = 0.0
    attr_gap_s: float = 0.0
    _t_attr: float = 0.0
    #: the ``engine.prefill_chunk`` span of this request's last chunk: the
    #: frontier stands at its start until the next charge reads its wall
    _s_chunk: object = None
    #: streaming: each iteration's newly committed tokens, and the end
    #: of the request, go to ``Engine.stream_sink``; False = blocking.
    #: ``submit(stream=...)`` as given: True, or the sink's own object for
    #: the request (the frontend's writer keeps the stream's state here).
    stream: object = False
    # -- chunked-prefill state (engine thread only) --
    _fill_buf: np.ndarray | None = dataclasses.field(
        default=None, repr=False
    )
    _fill_next: int = 0             # next chunk's first absolute position
    _fill_pad: int = 0              # padded prefill extent
    _prefill_done: bool = False
    _t_last_token: float = 0.0
    _done: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False
    )
    _rng: np.random.Generator | None = dataclasses.field(
        default=None, repr=False
    )

    def wait(self, timeout: float | None = None) -> bool:
        """Block until the request reaches a terminal state."""
        return self._done.wait(timeout)

    @property
    def ttft_s(self) -> float:
        return max(self.t_first_token - self.t_submit, 0.0)

    @property
    def e2e_s(self) -> float:
        return max(self.t_done - self.t_submit, 0.0)

    @property
    def tpot_s(self) -> float:
        """Mean per-output-token latency after the first token."""
        if len(self.tokens) <= 1:
            return 0.0
        return max(self.t_done - self.t_first_token, 0.0) / (
            len(self.tokens) - 1
        )


@dataclasses.dataclass
class _PrefillLaunch:
    """One spend of the prefill budget: the chunks it launched and what
    they add to the step record of the iteration whose budget they are.
    ``ahead`` says where the launch ran: in its own iteration's
    ``engine.prefill``, or under the decode step of the iteration before
    (a leaf of that iteration's ``engine.decode``), to be collected by
    its own."""

    ahead: bool = False
    chunks: int = 0
    #: the chunks' real tokens, and the (query, key) pairs they attend in a
    #: layer that keeps every row
    tokens: int = 0
    pairs: int = 0
    #: (a cache with latent rows) the rows the chunks' queries walk, and
    #: the positions of a query's scores the selection of its chunks past
    #: ``kv.index_topk`` walked (the kernel's longest walk x latent layers)
    context: int = 0
    select_walked: int = 0
    #: (a cache with chunk summaries) the summary rows the chunks attended
    summaries: int = 0
    #: the budget ran out with fillers still pending (the record's
    #: ``budget_stall``): decided where the launch is accounted
    stalled: bool = False
    #: ``(request, its last chunk's logits)`` of the prompts that ended
    #: among the chunks and wait for their first token (``ahead`` only: a
    #: launch in its own iteration samples it where the last chunk went)
    finished: list = dataclasses.field(default_factory=list)


#: the launch of an iteration that ran no prefill chunk
_NO_PREFILL = _PrefillLaunch()


class Engine:
    """Continuous-batching scheduler over the compiled serving
    programs (``serve.model``).  See the module docstring for the loop
    contract; construct, :meth:`start`, :meth:`submit` from any thread,
    :meth:`stop` to drain."""

    def __init__(
        self,
        params,
        cfg,
        *,
        max_slots: int = 4,
        max_queue: int = 64,
        block_size: int = 16,
        num_blocks: int | None = None,
        window_blocks: int | None = None,
        prefill_chunk: int = 16,
        prefill_budget: int | None = None,
        prefix_cache: bool = False,
        fused_sampling: bool = False,
        speculate: int = 0,
        spec_ngram: int = 3,
        max_context: int | None = None,
        max_new_cap: int | None = None,
        logdir: str | None = None,
        log_every: int = 50,
        step_ring: int = 512,
        registry=None,
        capture=None,
    ):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        max_context = max_context or cfg.max_seq
        if max_context % block_size:
            raise ValueError(
                f"max_context={max_context} must be a multiple of "
                f"block_size={block_size}"
            )
        if not 0 < prefill_chunk <= max_context:
            # even a 1-token prompt pads to one prefill chunk — a chunk
            # wider than the context would 400 every request at submit
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be in "
                f"[1, max_context={max_context}]"
            )
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(
                f"prefill_budget={prefill_budget} must be >= 1 tokens "
                "(None = unbudgeted)"
            )
        speculate = int(speculate)
        if speculate < 0:
            raise ValueError(f"speculate={speculate} must be >= 0")
        if speculate and not fused_sampling:
            # Speculation verifies + rejection-samples on device; a host
            # sampler would re-introduce the per-token round-trip the
            # draft window exists to amortize.
            raise ValueError("speculate requires fused_sampling=True")
        if speculate and spec_ngram < 1:
            raise ValueError(f"spec_ngram={spec_ngram} must be >= 1")
        #: params stay the caller's (possibly mesh-sharded) arrays — GSPMD
        #: partitions the programs exactly as it does models.generate.
        self.params = params
        self.cfg = dataclasses.replace(cfg, max_seq=max_context)
        self.max_slots = max_slots
        self.max_queue = max_queue
        self.max_new_cap = max_new_cap
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = prefill_budget
        self.prefix_cache = bool(prefix_cache)
        self.logdir = logdir
        self.log_every = max(int(log_every), 1)

        # layers in groups by attention kind, a pool and a page table
        # each (a window group holds a ring, not the whole context); a
        # model of one kind is one full group
        self.kv = make_grouped_cache(
            self.cfg, max_slots=max_slots, block_size=block_size,
            max_context=max_context, write_ahead=prefill_chunk,
            num_blocks={"full": num_blocks, "window": window_blocks},
        )
        if self.prefix_cache and self.kv.state is not None:
            raise ValueError(
                "prefix_cache is not implemented over a state group yet (a "
                "shared prefix has no snapshot of the state at its end): "
                "serve it without")
        if self.prefix_cache and not self.kv.shares_prefixes:
            raise ValueError(
                "prefix_cache is not implemented for a model of several "
                f"layer groups yet ({', '.join(self.kv.groups)}: each would "
                "need its own prefix index): serve it without")
        self.programs = make_programs(
            self.cfg, chunk=prefill_chunk, block_size=block_size,
            layers=self.kv.layers)
        if self.prefix_cache:
            self.programs.check_prefix_cache()
        #: the newest decode iteration's expert-routing counters (None
        #: from programs without expert layers): pairs on held experts,
        #: held experts hit (both summed over the expert layers), largest
        #: load of one expert; from the programs of a stack that is run
        #: several times (``programs.passes`` > 1) the passes' exit mass
        self._routed = None
        self._blocks_recycled0 = 0
        #: the group of chunk summaries (rows that stand for several tokens
        #: each), or None, and its counters at the last step record
        self._summaries = next(
            (g for g in self.kv.paged.values() if g.tokens_per_row > 1), None)
        self._closed0 = (0, 0)
        self.fused_sampling = bool(fused_sampling)
        self.speculate = speculate
        self.spec_ngram = int(spec_ngram)
        self._fused1 = None
        self._fused_spec = None
        if self.fused_sampling:
            # T=1 fused program (always) + the T=K+1 verify program: an
            # iteration where no slot drafted runs the cheap one-token
            # program, so a zero-hit-rate workload pays only the lookup.
            self._fused1 = self.programs.fused(0)
            if self.speculate:
                self._fused_spec = self.programs.fused(self.speculate)
            # Device-resident sampling state: last sampled token and the
            # per-request base PRNG key per slot (set at admission /
            # prefill completion; read every step with no host feed).
            # Tokens carry the (B, 1) feed shape the program consumes.
            self._dev_tokens = jnp.zeros((max_slots, 1), jnp.int32)
            self._dev_keys = jnp.zeros((max_slots, 2), jnp.uint32)
        # Per-step host->device traffic diet: the per-slot sampling
        # params and the active mask only change when the slot set does
        # (admission / prefill completion / eviction), and the page
        # tables only on admit/release/CoW — cache the device/host
        # copies behind dirty flags instead of re-shipping every step.
        self._slot_meta_dirty = True
        self._active_arr = np.zeros((max_slots,), bool)
        self._dev_active = jnp.asarray(self._active_arr)
        self._dev_temp = jnp.zeros((max_slots,), jnp.float32)
        self._dev_topk = jnp.zeros((max_slots,), jnp.int32)
        self._dev_prompt_lens = jnp.zeros((max_slots,), jnp.int32)
        self._dev_zero_drafts = jnp.zeros((max_slots,), jnp.int32)
        self._dev_tables = None
        self._dev_tables_version = -1

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: collections.deque[GenRequest] = collections.deque()
        self._ids = itertools.count()
        self._slots: list[GenRequest | None] = [None] * max_slots
        self._slot_reused = [False] * max_slots  # slot saw a previous request
        #: admitted-but-unfilled requests, round-robin order (the budget
        #: scheduler's working set; entries are also in _slots).
        self._filling: collections.deque[GenRequest] = collections.deque()
        self._last_tokens = np.zeros((max_slots,), np.int32)
        self._thread: threading.Thread | None = None
        self._stop_flag = False
        self._crashed: str | None = None  # loop-death reason (healthz/submit)
        self._stopped = False             # clean shutdown: refuse new work
        self.decode_steps = 0
        self.occupancy_max = 0
        self.prefill_iters = 0   # iterations that ran >= 1 prefill chunk
        self.prefill_chunks = 0  # chunks run across all iterations
        #: of those chunks, the ones launched under the decode step of the
        #: iteration before theirs (the per-step ``prefill_prelaunched``)
        self.prefill_prelaunched = 0
        #: iterations where the prefill budget ran out with fillers still
        #: pending (the per-step ``budget_stall`` flag, accumulated).
        self.prefill_budget_stalls = 0
        # engine step log (request-path observability): every step()
        # iteration that did work appends one structured record to this
        # bounded ring (the GET /stepz tail) and, with a logdir, to
        # steps.jsonl.  Ring appends/reads happen under _log_lock so
        # /stepz snapshots never race the engine thread.
        self.step_ring_size = max(int(step_ring), 1)
        self._step_ring: collections.deque = collections.deque(
            maxlen=self.step_ring_size)
        self._step_id = 0
        #: the open iteration's ``obs.tracing.tiled`` (engine thread only):
        #: the phases below name their leaves through it
        self._tiles = None
        # The engine thread's account between two step records (wall on
        # the spans' clock, CPU by ``time.thread_time``), from the start
        # of one ``engine.log`` to the start of the next; reset when
        # another thread takes over ``step()`` (thread CPU clocks do not
        # compare).
        self._tid = None
        self._mark_wall = 0.0      # start of the previous engine.log
        self._mark_cpu = 0.0
        self._mark_gc = 0.0
        self._log_prev_s = 0.0     # the previous engine.log's wall
        self._wait_s = 0.0         # engine.wait since the previous step
        self._cpu_blocked = 0.0    # thread CPU inside the blocking leaves
        self._cpu_leaf0 = 0.0      # thread CPU where .fetch / .commit began
        self._recent_walls: collections.deque = collections.deque(
            maxlen=STALL_HISTORY)
        # What JAX traced, lowered, compiled or loaded (the compile log,
        # obs.tracing): an iteration takes what ended inside it into its
        # step record, and ``state()`` keeps the account.
        obs_tracing.install_compile_log()
        self._compiles = {"count": 0, "seconds": 0.0, "last_program": None,
                          "last_t": None}
        #: The entry point's start-up phases (obs.PhaseTrace) with
        #: ``startup.first_request`` open, or None: the engine names the
        #: children below as they end, in this order, and the last ends
        #: start-up.
        self.startup_trace: obs_tracing.PhaseTrace | None = None
        self._startup_next = ["startup.first_wait", "startup.first_chunk",
                              "startup.first_decode"]
        #: lags of the lines the stream writer has written since the
        #: last record (``note_stream_line`` appends, ``engine.log``
        #: drains: no lock on either side)
        self._stream_lags: collections.deque = collections.deque()
        #: where the streaming requests' lines go: ``sink(batch)``, called
        #: on the engine thread once for all the streams an iteration
        #: committed tokens for (once more if requests ended in it, with
        #: their ends; and once a first token), ``batch`` a list
        #: of ``(request, tokens, stamp)`` — ``stamp`` the ``time.time()``
        #: of the commit, which the sink measures its lag from
        #: (:meth:`note_stream_line`); ``tokens`` None says the request
        #: reached its end (its status is final).  It must not block:
        #: ``ServeServer`` sets it to its writer thread's ``put``.  None
        #: drops the lines (the tokens stay on the requests).
        self.stream_sink = None
        self._stream_out: list = []    # the batch being gathered
        if _gc_callback not in gc.callbacks:
            gc.callbacks.append(_gc_callback)
        self._step_evicted = 0     # requests finished in the current step
        #: the current step's (device_sampled, logits_fetched)
        self._step_sampled = (0, 0)
        #: the current step's (latent_rows_read, index_rows_scored): the
        #: latent rows its decode iteration read (attended tokens x latent
        #: layers: where an indexer selects them, ``kv.index_topk`` a slot a
        #: layer at most) and the index keys it scored to select them (every
        #: cached token x latent layers); counted only where a group stores
        #: latent rows
        self._step_latent = (0, 0)
        #: the stretches the latent decode kernel walked in the current
        #: step, and the stretches the slots' table rows can hold, both x
        #: latent layers: a decoding slot's walk is ``ceil(rows it attends /
        #: PAGED_LATENT_STRETCH)`` trips (an idle slot attends nothing, a
        #: grid step of no trip, and is not counted), so the pair says
        #: what share of the table held rows; counted only where the decode
        #: program attends through ``paged_latent_attn`` (a capacity of 0
        #: elsewhere)
        self._step_walk = None
        self._walk_capacity = (
            self.kv.latent_layers * self.kv.max_slots
            * -(-self.kv.max_context // PAGED_LATENT_STRETCH)
            if self.programs.decode_attention == "paged_latent_attn" else 0)
        #: the same pair of the ``paged_attn`` kernel's walks, summed over
        #: the paged groups x their layers: a decoding slot's walk in a group
        #: is the trips from the stretch of its first attended row to its
        #: last (``PagedKVCache.span_attended``; an idle slot attends no row
        #: of any group and is not counted), the capacity the
        #: most trips the slots' walks can take (a table row's stretches; a
        #: window group's ``ceil((window + stretch - 1) / stretch)``); counted
        #: only where every group's decode attends through ``paged_attn``
        self._step_paged_walk = None
        self._paged_capacity = sum(
            len(self.kv.layers[name]) * self.kv.max_slots * -(-min(
                g.block_tables.shape[1] * g.block_size,
                getattr(g, "window", np.inf) + PAGED_STRETCH - 1)
                // PAGED_STRETCH)
            for name, g in self.kv.paged.items()
        ) if self.programs.decode_attention == "paged_attn" else 0
        #: the current step's {group: K/V rows its decode iteration
        #: attended}; counted only over several paged groups
        self._step_rows_read: dict[str, int] = {}
        #: the prefill launch the current step accounts for (its budget's:
        #: the chunks of the step record), and the one launched under the
        #: current step's decode step for the next iteration to collect
        self._step_prefill = _NO_PREFILL
        self._pending: _PrefillLaunch | None = None
        #: ``obs.capture.CaptureEngine`` (or None): the engine loop opens
        #: and closes its profiler windows by iteration, so a capture
        #: armed through ``POST /profilez?steps=N`` holds N iterations.
        self.capture = capture
        # prefix_lookups/hits/cached_tokens live on the PagedKVCache (the
        # admission path that owns the success-only counting rule) — one
        # source of truth, surfaced via kv.stats(); only the engine-level
        # logical split (uncached prompt tokens) is counted here.
        self.counters = {
            "submitted": 0, "ok": 0, "rejected": 0, "error": 0,
            "tokens_generated": 0, "admits": 0, "admits_into_freed_slot": 0,
            "prefill_tokens": 0,
            # decode fast path (ISSUE 15): tokens committed by decode /
            # verify steps, draft proposals and acceptances, and the
            # dispatch accounting the bench A/Bs — decode program
            # executions plus host sampling rounds (the iterations that
            # fetched the logits and ran the numpy sampler, for a request
            # with temperature > 0; none in greedy traffic, none fused).
            "decode_tokens": 0, "spec_drafted": 0, "spec_accepted": 0,
            "decode_dispatches": 0, "host_sample_rounds": 0,
            # decode iterations that fetched the logits (== the rounds
            # above: a request with temperature > 0 was decoding), and the
            # slot-iterations whose token came off the device with the
            # step (all of them where nobody samples)
            "logit_fetches": 0, "device_sampled_tokens": 0,
            # slot-steps = sum of active slots over decode steps: the
            # denominator that makes tokens-per-step PER-SLOT (1.0
            # without speculation, matching the histogram), not an
            # occupancy echo.
            "slot_steps": 0,
        }

        reg = registry or obs_registry.default_registry()
        self._m_ttft = reg.histogram(
            "serve_ttft_seconds", "request arrival -> first token")
        self._m_tpot = reg.histogram(
            "serve_tpot_seconds", "mean per-output-token latency")
        self._m_e2e = reg.histogram(
            "serve_e2e_seconds", "request arrival -> completion")
        self._m_occ = reg.histogram(
            "serve_batch_occupancy", "active slots per decode step",
            buckets=tuple(float(i) for i in range(1, max_slots + 1)),
        )
        self._m_queue = reg.gauge("serve_queue_depth", "queued requests")
        self._m_active = reg.gauge("serve_active_slots", "occupied slots")
        self._m_blocks_free = reg.gauge(
            "serve_kv_blocks_free", "free KV pool blocks")
        self._m_blocks_cached = reg.gauge(
            "serve_kv_blocks_cached",
            "refcount-0 prefix-cached KV blocks (evictable)")
        self._m_block_refs = reg.gauge(
            "serve_kv_block_refs",
            "sum of block refcounts (> used blocks = sharing live)")
        self._m_frag = reg.gauge(
            "serve_kv_fragmentation",
            "internal fragmentation of allocated KV blocks [0,1]")
        self._m_prefix_occ = reg.gauge(
            "serve_prefix_cache_occupancy",
            "share of the pool holding indexed prefix content [0,1]")
        self._m_prefix_rate = reg.gauge(
            "serve_prefix_hit_rate",
            "admissions that mapped >=1 cached prefix block [0,1]")
        self._m_requests = reg.counter(
            "serve_requests_total", "terminal requests by status")
        self._m_tokens = reg.counter(
            "serve_tokens_generated_total", "generated tokens")
        self._m_admits = reg.counter(
            "serve_admits_total", "admissions (reused=slot had served before)")
        self._m_prefix_hits = reg.counter(
            "serve_prefix_hits_total",
            "admissions that mapped >=1 cached prefix block")
        self._m_prefix_tokens = reg.counter(
            "serve_prefix_cached_tokens_total",
            "prompt tokens served from the prefix cache (no prefill)")
        self._m_prefill_tokens = reg.counter(
            "serve_prefill_tokens_total",
            "prompt tokens owed to prefill compute (uncached)")
        self._m_evictions = reg.counter(
            "serve_prefix_evictions_total",
            "cached blocks evicted under pool pressure")
        self._m_cow = reg.counter(
            "serve_kv_cow_copies_total", "copy-on-write block copies")
        self._m_spec_drafted = reg.counter(
            "serve_spec_drafted_total",
            "draft tokens proposed to the speculative verifier")
        self._m_spec_accepted = reg.counter(
            "serve_spec_accepted_total",
            "draft tokens accepted by the verifier (always <= drafted)")
        #: whether a decode iteration's attended rows are counted a group:
        #: where the layers lie in more than one paged group (window layers
        #: beside full ones), whose reads differ by the window
        self._count_rows = len(self.kv.paged) > 1
        if self._count_rows:
            self._m_rows_read = {
                name: reg.counter(
                    f"serve_{name}_rows_read_total",
                    f"K/V rows the decode iterations attended in the {name} "
                    "group: a slot's length (at most the window) x its "
                    "layers")
                for name in self.kv.paged}
        if self.kv.index_topk:
            self._m_latent_read = reg.counter(
                "serve_latent_rows_read_total",
                "latent rows the decode iterations attended: the indexer's "
                "selection, index_topk a slot a layer at most")
            self._m_index_scored = reg.counter(
                "serve_index_rows_scored_total",
                "index keys the decode iterations scored to select them")
        self._m_stream_lag = reg.histogram(
            "serve_stream_lag_seconds",
            "a streamed line: tokens committed -> socket write returned")
        self._m_tok_step = reg.histogram(
            "serve_decode_tokens_per_step",
            "tokens committed per slot per decode step (1 without "
            "speculation; up to speculate+1 with an accepted burst)",
            buckets=tuple(
                float(i) for i in range(1, max(self.speculate, 1) + 2)
            ),
        )
        self._last_evictions = 0  # registry-counter delta trackers
        self._last_cow = 0
        self._registry = reg

        self._req_log = None
        self._met_log = None
        self._step_log = None
        self._log_lock = threading.Lock()
        if logdir:
            os.makedirs(logdir, exist_ok=True)
            self._req_log = open(os.path.join(logdir, "requests.jsonl"), "a")
            self._met_log = open(os.path.join(logdir, "metrics.jsonl"), "a")
            self._step_log = open(os.path.join(logdir, "steps.jsonl"), "a")

    # -- submission (any thread) ---------------------------------------------

    def submit(
        self,
        prompt,
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: int = 0,
        eos_token_id: int | None = None,
        seed: int = 0,
        trace_id: str | None = None,
        tenant: str | None = None,
        deadline_s: float | None = None,
        stream: object = False,
    ) -> GenRequest:
        """Validate + enqueue; returns the live :class:`GenRequest`.

        Raises ``ValueError`` on a malformed request (frontend: 400),
        :class:`QueueFullError` on backpressure (frontend: 429), and
        ``RuntimeError`` once the scheduler loop has died (frontend: 503
        — queueing onto a loop nothing drains would strand the client
        for its whole timeout)."""
        if self._crashed is not None:
            raise RuntimeError(f"engine loop dead: {self._crashed}")
        if self._stopped:
            # A late HTTP handler racing serve.py shutdown must be
            # refused, not queued onto a loop nothing drains.
            raise RuntimeError("engine stopped")
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must be a non-empty token list")
        if any(t < 0 or t >= self.cfg.vocab_size for t in prompt):
            raise ValueError(
                f"prompt tokens must be in [0, {self.cfg.vocab_size})"
            )
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        # Sampling parameters are validated HERE, not on the engine loop
        # thread: a bad value must 400 one request, never kill the loop.
        temperature = float(temperature)
        if not math.isfinite(temperature) or temperature < 0.0:
            raise ValueError(
                f"temperature must be a finite number >= 0, got {temperature}"
            )
        top_k = int(top_k)
        if not 0 <= top_k <= self.cfg.vocab_size:
            raise ValueError(
                f"top_k must be in [0, {self.cfg.vocab_size}], got {top_k}"
            )
        if self.max_new_cap and max_new_tokens > self.max_new_cap:
            raise ValueError(
                f"max_new_tokens {max_new_tokens} exceeds the server cap "
                f"{self.max_new_cap}"
            )
        if eos_token_id is not None and not (
            0 <= eos_token_id < self.cfg.vocab_size
        ):
            raise ValueError(f"bad eos_token_id {eos_token_id}")
        if trace_id is not None:
            trace_id = str(trace_id)
            if not 1 <= len(trace_id) <= 64:
                raise ValueError(
                    f"trace_id must be 1..64 characters, got "
                    f"{len(trace_id)}"
                )
        # Validated BEFORE GenRequest construction so even the rejected
        # path's requests.jsonl row carries a well-formed identity.
        tenant = validate_tenant(tenant)
        if deadline_s is not None:
            deadline_s = float(deadline_s)
            if not math.isfinite(deadline_s) or deadline_s <= 0:
                raise ValueError(
                    f"deadline_s must be a finite number > 0, got "
                    f"{deadline_s}"
                )
        # The footprint is prefix-cache-independent (the chunk grid stays
        # anchored at position 0), so the worst case is checkable at
        # submit time without peeking at the engine thread's index state.
        footprint = self._footprint(len(prompt), max_new_tokens)
        if footprint > self.kv.max_context:
            raise ValueError(
                f"request footprint {footprint} tokens (prompt "
                f"{len(prompt)} padded to the {self.prefill_chunk}-token "
                f"prefill chunk, + {max_new_tokens} new) exceeds "
                f"max_context={self.kv.max_context}"
            )
        # An oversubscribed pool may be smaller than one max_context slot:
        # a request the WHOLE pool can't hold would wedge the strict-FIFO
        # queue head forever — reject it at the door instead.
        self.kv.check_fits(footprint)
        req = GenRequest(
            id=f"r{next(self._ids)}", prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            temperature=float(temperature), top_k=int(top_k),
            eos_token_id=eos_token_id, seed=int(seed),
            trace_id=trace_id or obs_tracing.new_trace_id(),
            tenant=tenant,
            t_submit=time.time(), stream=stream,
        )
        if deadline_s is not None:
            req.t_deadline = req.t_submit + deadline_s
        req._rng = np.random.default_rng(req.seed)
        rejected = False
        with self._cond:
            # Re-checked under the lock: a submit racing stop() past the
            # unlocked guard above must not enqueue onto a drained queue.
            if self._stopped or self._stop_flag or self._crashed is not None:
                raise RuntimeError("engine stopped")
            if len(self._queue) >= self.max_queue:
                rejected = True
                req.status = "rejected"
                req.t_done = time.time()
                req._done.set()
                self.counters["rejected"] += 1
                self._m_requests.inc(status="rejected")
            else:
                self.counters["submitted"] += 1
                self._queue.append(req)
                self._m_queue.set(len(self._queue))
                self._cond.notify()
        if rejected:
            # The disk write happens OUTSIDE the scheduler lock: a 429
            # storm must not stall the decode loop on log I/O.
            self._log_request(req)
            raise QueueFullError(
                f"queue full ({self.max_queue} requests waiting)"
            )
        return req

    def generate(self, prompt, *, timeout: float | None = None,
                 **kwargs) -> GenRequest:
        """Blocking convenience: submit + wait (tests, bench)."""
        req = self.submit(prompt, **kwargs)
        if not req.wait(timeout):
            raise TimeoutError(f"request {req.id} still running")
        return req

    # -- scheduler (engine thread) -------------------------------------------

    def _refresh_slot_meta(self) -> None:
        """Rebuild the cached per-slot sampling-param / active-mask
        DEVICE arrays after a slot-set change (admission, prefill
        completion, eviction; engine thread only).  These are the
        decode inputs that do not change between slot-set changes —
        caching them takes the per-step host->device transfers down to
        the two that genuinely change every step (seq_lens and, on the
        speculative path, the draft window)."""
        if not self._slot_meta_dirty:
            return
        for i, r in enumerate(self._slots):
            self._active_arr[i] = r is not None and r._prefill_done
        self._dev_active = jnp.asarray(self._active_arr)
        if self.fused_sampling:
            self._dev_temp = jnp.asarray(np.array(
                [0.0 if r is None else r.temperature for r in self._slots],
                np.float32))
            self._dev_topk = jnp.asarray(np.array(
                [0 if r is None else r.top_k for r in self._slots],
                np.int32))
            self._dev_prompt_lens = jnp.asarray(np.array(
                [0 if r is None else len(r.prompt) for r in self._slots],
                np.int32))
        self._slot_meta_dirty = False

    def _tables_dev(self):
        """Device copy of the page tables, re-shipped only when a table
        actually changed (``PagedKVCache.tables_version``)."""
        if self._dev_tables_version != self.kv.tables_version:
            # copies: a window group's table changes while a program
            # that was handed it may still be running, and on the CPU
            # jnp.asarray can alias the numpy buffer
            self._dev_tables = {
                name: jnp.asarray(g.block_tables.copy())
                for name, g in self.kv.groups.items()}
            self._dev_tables_version = self.kv.tables_version
        return self._dev_tables

    def _seq_lens_dev(self):
        """The slots' resident-token counts for a decode launch.  A copy:
        the chunks launched behind the step advance their slot's count
        (``note_written``) while the step may not have started, and on
        the CPU jnp.asarray can alias the numpy buffer."""
        return jnp.asarray(self.kv.seq_lens.copy())

    def _padded_prompt_len(self, prompt_len: int) -> int:
        """Prompt length rounded up to whole prefill chunks — the extent
        the prefill program actually writes K/V through (pad positions
        included), so reservations MUST be sized from this same number."""
        c = self.prefill_chunk
        return -(-prompt_len // c) * c

    def _chunk_real_tokens(self, prompt_len: int, start: int) -> int:
        """How many tokens of the chunk at ``start`` are the prompt's (the
        rest of its ``prefill_chunk`` positions are padding): the one place
        that says where a prompt ends inside the chunk grid."""
        return min(max(prompt_len - start, 0), self.prefill_chunk)

    def _footprint(self, prompt_len: int, max_new: int) -> int:
        """Worst-case KV positions a request can touch: the padded prompt
        (the final prefill chunk writes pad K/V) or the full generation,
        whichever is larger.  Independent of any prefix-cache hit: the
        chunk grid is anchored at position 0, so a partially cached
        prompt still spans the same padded extent."""
        return max(self._padded_prompt_len(prompt_len),
                   prompt_len + max_new)

    def step(self) -> bool:
        """One scheduler iteration: admit → budgeted prefill → decode →
        evict.  Public so tests can drive the engine synchronously;
        returns True when any work happened.  Every iteration that did
        work leaves one step-log record (ring + steps.jsonl).  Its prefill
        is the launch the iteration before made ahead for it
        (:meth:`_to_fetch`), collected here, or — nothing pending: no
        decode step ran before it, or no budget — launched in line."""
        if not (self._queue or self._filling
                or any(r is not None for r in self._slots)):
            # nothing queued, filling or decoding: no iteration to name
            # (the gauges were set when the last request left)
            return False
        if self.startup_trace is not None:
            self._startup_mark("startup.first_wait")
        tokens0 = self.counters["decode_tokens"]
        drafted0 = self.counters["spec_drafted"]
        accepted0 = self.counters["spec_accepted"]
        self._step_evicted = 0
        self._step_sampled = (0, 0)
        self._step_latent = (0, 0)
        self._step_walk = self._step_paged_walk = None
        self._step_rows_read = {}
        # The iteration is one span tree (mirrored into any open profiler
        # trace) whose leaves tile it: a leaf begins where the one before
        # it ended (`obs.tracing.tiled`).  The step record's walls are its
        # durations, and the `step` attribute is the steps.jsonl `step`
        # this iteration gets.
        with obs_tracing.tiled("engine.step", "engine.admit",
                               step=self._step_id + 1) as tiles:
            self._tiles = tiles
            root = tiles.root
            tid = threading.get_ident()
            if tid != self._tid:
                self._adopt_thread(tid, root.t0)
            admitted = self._admit_from_queue()
            launch, self._pending = self._pending, None
            if launch is not None:
                # its budget was spent where it was launched
                self._collect_prefill(launch)
            elif self._filling:
                launch = self._launch_prefill()
            else:
                launch = _NO_PREFILL
            self._step_prefill = launch
            occupancy = sum(
                r is not None and r._prefill_done for r in self._slots
            )
            if occupancy:
                # closes the leaf that held the census (admit, or the
                # last of engine.prefill) and engine.prefill with it
                tiles.to("engine.decode", "engine.decode.dispatch")
                prefill = root.children[-1]
                self._run_decode_step(
                    prefill.dur_s if prefill.name == "engine.prefill"
                    else 0.0)
            did = bool(admitted or launch.chunks or occupancy)
            if did:
                s_log = tiles.to("engine.log")
                cpu_now = time.thread_time()
                # Post-eviction census at `now` — the same instant
                # and slot set the step record's active_slots reflects.
                now = time.time()
                # step_s: the work, not the log
                step_s, walls = self._iteration_walls(root, s_log, cpu_now)
                compile_s, compiled = obs_tracing.take_compiled()
                walls["compile_s"] = compile_s
                if compile_s:
                    walls["compiled"] = compiled
                    self._note_compiled(compile_s, compiled, now)
                self._log_step(
                    now, walls, admitted, occupancy,
                    self.counters["decode_tokens"] - tokens0,
                    self.counters["spec_drafted"] - drafted0,
                    self.counters["spec_accepted"] - accepted0,
                    sum(self.kv.billed_blocks(i)
                        for i, r in enumerate(self._slots) if r is not None),
                )
                self._note_stall(root, step_s + self._log_prev_s, now,
                                 compile_s)
                if self.decode_steps % self.log_every == 0:
                    self._log_metrics_row()
        self._tiles = None
        if did:
            self._log_prev_s = s_log.dur_s
            if occupancy and self.startup_trace is not None:
                self._startup_mark("startup.first_decode")
        return did

    def _startup_mark(self, name: str) -> None:
        """Child ``name`` of ``startup.first_request`` ends here, if it has
        not yet: ``startup.first_wait`` where the first iteration with work
        begins, ``startup.first_chunk`` with the first request's first
        token (its prefill program compiled, or loaded, and ran), and
        ``startup.first_decode`` with the first iteration that decoded
        (the one-token program likewise).  That one closes
        ``startup.first_request`` and start-up: ``startup.ready``."""
        if name not in self._startup_next:
            return
        startup = self.startup_trace
        while True:     # (a trace handed over mid-request names all to here)
            child = self._startup_next.pop(0)
            startup.mark(child, parent="startup.first_request")
            if child == name:
                break
        if not self._startup_next:
            self.startup_trace = None
            startup.close("startup.first_request", step=self._step_id)
            startup.ready()

    def _note_compiled(self, seconds: float, names: str, now: float) -> None:
        c = self._compiles
        c["count"] += names.count(",") + 1
        c["seconds"] = round(c["seconds"] + seconds, 6)
        c["last_program"] = names.rsplit(",", 1)[-1]
        c["last_t"] = now

    def _adopt_thread(self, tid: int, t0: float) -> None:
        """Another thread runs ``step()`` from here on (the loop thread
        after a synchronous warm-up, a test's): the account between two
        records starts anew at ``t0``, this iteration's start."""
        self._tid = tid
        obs_tracing.take_compiled()    # what compiled before is not its
        self._mark_wall = t0
        self._mark_cpu = time.thread_time()
        self._mark_gc = _gc_seconds.setdefault(tid, 0.0)
        self._log_prev_s = self._wait_s = self._cpu_blocked = 0.0

    def _iteration_walls(self, root, s_log,
                         cpu_now: float) -> tuple[float, dict[str, float]]:
        """``step_s`` and the step record's seconds (rounded as the record
        holds them), read off the iteration's span tree at the start of
        ``engine.log`` (``s_log``; ``cpu_now`` is the engine thread's CPU
        clock there), and the account since the previous record moved on
        to here.

        The leaves tile ``step_s`` (``unnamed_s`` is what they leave:
        rounding), and a record's ``log_prev_s + between_s + wait_s +
        step_s`` is the wall from the previous record's ``engine.log`` to
        this one's: the records tile the engine thread's life.
        ``prelaunch_s`` is the wall of the ``engine.prefill_chunk`` leaves
        inside ``engine.decode``, between ``.dispatch`` and ``.fetch``: the
        next iteration's chunks, launched under this one's decode step
        (their counts are the next record's).
        ``offcpu_s`` is that wall less the leaves the thread blocks in by
        design (``engine.wait``, ``.fetch``, ``engine.first_token``), less
        the thread's CPU seconds outside them: time it had work and did
        not run (it waited for the interpreter, or sat in a system
        call)."""
        step_s = s_log.t0 - root.t0
        admit_s = prefill_s = decode_s = first_token_s = 0.0
        dispatch_s = prelaunch_s = fetch_s = commit_s = named = 0.0
        for phase in root.children:     # engine.log is still open
            name = phase.name
            if name == "engine.admit":
                admit_s = named = phase.dur_s
            elif name == "engine.prefill":
                prefill_s = phase.dur_s
                for leaf in phase.children:
                    named += leaf.dur_s
                    if leaf.name == "engine.first_token":
                        first_token_s += leaf.dur_s
            else:
                decode_s = phase.dur_s
                dispatch, *ahead, fetch, commit = phase.children
                dispatch_s, fetch_s = dispatch.dur_s, fetch.dur_s
                commit_s = commit.dur_s
                prelaunch_s = sum(leaf.dur_s for leaf in ahead)
                named += dispatch_s + prelaunch_s + fetch_s + commit_s
        wait_s, log_prev_s = self._wait_s, self._log_prev_s
        wall = s_log.t0 - self._mark_wall
        cpu = cpu_now - self._mark_cpu - self._cpu_blocked
        gc_total = _gc_seconds[self._tid]
        walls = {
            "admit_s": round(admit_s, 6),
            "prefill_s": round(prefill_s, 6),
            "decode_s": round(decode_s, 6),
            "step_s": round(step_s, 6),
            "dispatch_s": round(dispatch_s, 6),
            "prelaunch_s": round(prelaunch_s, 6),
            "fetch_s": round(fetch_s, 6),
            "commit_s": round(commit_s, 6),
            "first_token_s": round(first_token_s, 6),
            "log_prev_s": round(log_prev_s, 6),
            "between_s": round(wall - log_prev_s - wait_s - step_s, 6),
            "wait_s": round(wait_s, 6),
            "offcpu_s": round(max(
                wall - wait_s - fetch_s - first_token_s - cpu, 0.0), 6),
            "commit_cpu_s": round(cpu_now - self._cpu_leaf0, 6)
            if decode_s else 0.0,
            "gc_s": round(gc_total - self._mark_gc, 6),
            "unnamed_s": round(max(step_s - named, 0.0), 6),
        }
        self._mark_wall, self._mark_cpu = s_log.t0, cpu_now
        self._mark_gc = gc_total
        self._wait_s = self._cpu_blocked = 0.0
        return step_s, walls

    def _note_stall(self, root, wall_s: float, now: float,
                    compile_s: float) -> None:
        """One ``engine_stall`` row in ``trace.jsonl`` for an iteration
        whose wall (with the ``engine.log`` before it) is far above the
        recent iterations': the step id, the record's ``t``, the span
        tree and ``compile_s``, the seconds of it JAX spent on a program
        (the tree's ``compile.*`` spans), so the stall is found without
        scanning ``steps.jsonl``."""
        recent = self._recent_walls
        if wall_s > STALL_MIN_S and len(recent) >= STALL_MIN_HISTORY:
            median = statistics.median(recent)
            rec = obs_tracing.active_recorder()
            if wall_s > STALL_FACTOR * median and rec is not None:
                tree = root.to_dict()       # engine.log is still open
                tree["dur_s"] = round(wall_s - self._log_prev_s, 6)
                rec.write_event({
                    "kind": "anomaly", "anomaly": "engine_stall", "t": now,
                    "step": self._step_id, "value": round(wall_s, 6),
                    "message": (
                        f"engine iteration {self._step_id} took "
                        f"{wall_s:.3f}s, {wall_s / median:.0f}x the "
                        f"median of the last {len(recent)}"),
                    "median_s": round(median, 6),
                    "log_prev_s": round(self._log_prev_s, 6),
                    "compile_s": compile_s,
                    "spans": [tree],
                })
        recent.append(wall_s)

    def _log_step(self, now: float, walls: dict[str, float],
                  admitted: list[GenRequest], occupancy: int,
                  tokens: int, drafted: int, accepted: int,
                  blocks_billed: float) -> None:
        """One structured record for the iteration that just ran: phase
        mix, occupancy, per-phase token deltas, the prefill chunks of its
        budget (``self._step_prefill``: ``prefill_prelaunched`` of them
        were launched under the decode step before), the lines the stream
        threads wrote since the previous record, and ``walls``, the
        seconds :meth:`_iteration_walls` read off the iteration's span
        tree, rounded (host wall and the engine thread's CPU, all of
        them: device time per phase is what a profiler trace holding these
        spans gives).
        ``blocks_billed`` is the pool's refcount-weighted block census at
        ``now``; admissions are additionally broken down by tenant."""
        prefill = self._step_prefill
        phases = []
        if admitted:
            phases.append("admit")
        if prefill.chunks:
            phases.append("prefill")
        if occupancy:
            phases.append("decode")
        lags = self._stream_lags
        lines, lag_max = len(lags), 0.0
        if lines:
            lag_max = round(max(lags.popleft() for _ in range(lines)), 6)
        self._step_id += 1
        rec = {
            "t": now,
            "step": self._step_id,
            "phase": "+".join(phases) or "idle",
            "occupancy": occupancy,
            "active_slots": sum(r is not None for r in self._slots),
            "filling_slots": self._filling_slots(),
            "queue_depth": len(self._queue),
            "admitted": len(admitted),
            "evicted": self._step_evicted,
            "prefill_chunks": prefill.chunks,
            "prefill_prelaunched": prefill.chunks if prefill.ahead else 0,
            "chunk_tokens": prefill.tokens,
            "chunk_pairs": prefill.pairs,
            "budget_stall": int(prefill.stalled),
            "tokens_committed": tokens,
            "spec_drafted": drafted,
            "spec_accepted": accepted,
            "device_sampled": self._step_sampled[0],
            "logits_fetched": self._step_sampled[1],
            **walls,
            "stream_lines": lines,
            "stream_lag_max_s": lag_max,
            "kv_blocks_billed": round(blocks_billed, 4),
        }
        rec.update(self._group_step_fields(occupancy))
        if admitted:
            by_tenant: dict[str, int] = {}
            for r in admitted:
                by_tenant[r.tenant] = by_tenant.get(r.tenant, 0) + 1
            rec["admitted_tenants"] = by_tenant
        with self._log_lock:
            # ring appended under the log lock so a /stepz snapshot
            # (HTTP thread) never races the engine thread's append;
            # t is stamped above on the single writer, so the stream
            # stays t-ordered (schema checker invariant)
            self._step_ring.append(rec)
            if self._step_log is None:
                return
            try:
                # every number of a record is finite but for a fault:
                # json_sanitize's walk (a Python call a field, each one a
                # profiler event in a traced run) is for that case only
                line = _encode_finite(rec)
            except ValueError:
                line = json.dumps(json_sanitize(rec))
            self._step_log.write(line + "\n")
            self._step_log.flush()

    def note_stream_line(self, stamp: float) -> None:
        """The stream writer handed the line of the tokens committed at
        ``stamp`` (``time.time()``) to its socket: the lag goes to
        ``serve_stream_lag_seconds`` and to the next step record."""
        lag = max(time.time() - stamp, 0.0)
        self._m_stream_lag.observe(lag)
        self._stream_lags.append(lag)

    def _group_step_fields(self, occupancy: int) -> dict:
        """Step-log fields of the layer groups and the expert layers:
        each pool's blocks in use, the blocks a window group let go since
        the last record, (decode iterations of programs with expert
        layers only) this iteration's routing counters, (a cache with
        latent rows only) the rows its prefill chunks walked and its
        decode iteration read, and (a cache with a state group only) the
        slots holding live state and the tokens that went through the
        recurrence."""
        fields = {}
        prefill = self._step_prefill
        if occupancy and self.programs.passes > 1:
            # a stack run several times: the decode program's fourth output
            # is the passes' exit mass, the active slots' mean of p_u
            fields["ut_steps"] = self.programs.passes
            for u, mass in enumerate(np.asarray(self._routed).tolist()):
                fields[f"ut_exit_mass_{u}"] = round(mass, 6)
        elif occupancy and self._routed is not None:
            pairs, hit, load, *groups = (
                int(v) for v in np.asarray(self._routed))
            fields.update(moe_pairs=pairs, moe_experts_hit=hit,
                          moe_max_load=load)
            if groups:      # group-limited routing only
                fields["moe_groups_hit"] = groups[0]
        if self.kv.latent_layers:
            read, scored = self._step_latent
            if prefill.context:
                fields["context_tokens"] = prefill.context
            if prefill.select_walked:
                fields["select_positions_walked"] = prefill.select_walked
            if occupancy:
                fields["latent_rows_read"] = read
                if self.kv.index_topk:
                    fields["index_rows_scored"] = scored
                if self._step_walk:
                    fields["latent_stretches_walked"] = self._step_walk
                    fields["latent_stretches_capacity"] = self._walk_capacity
        if occupancy:
            for name, read in self._step_rows_read.items():
                fields[f"{name}_rows_read"] = read
            if self._step_paged_walk:
                fields["paged_stretches_walked"] = self._step_paged_walk
                fields["paged_stretches_capacity"] = self._paged_capacity
        recycled = self.kv.blocks_recycled
        fields["kv_blocks_freed"] = recycled - self._blocks_recycled0
        self._blocks_recycled0 = recycled
        if self._summaries is not None:
            # a cache with chunk summaries beside a tumbling ring only: the
            # summary rows the programs wrote and the windows that closed
            # since the last record, and the summary rows this iteration's
            # prefill chunks attended (a layer)
            closed = (self.kv.summary_rows_written, self.kv.windows_closed)
            fields["summary_rows_written"] = closed[0] - self._closed0[0]
            fields["windows_closed"] = closed[1] - self._closed0[1]
            self._closed0 = closed
            if prefill.chunks:
                fields["chunk_summary_rows_read"] = prefill.summaries
        for name, g in self.kv.paged.items():
            fields[f"kv_blocks_used_{name}"] = g.allocator.used_blocks
        if self.kv.state is not None:
            fields["state_slots_used"] = int(self.kv.state.live.sum())
            # the real tokens of the chunks and one a decoding slot
            fields["scan_tokens"] = prefill.tokens + occupancy
        return fields

    def step_records(self, n: int | None = None) -> list[dict]:
        """Snapshot of the newest ``n`` step-log records (all retained
        records when ``n`` is None) — the ``GET /stepz`` live tail."""
        with self._log_lock:
            recs = list(self._step_ring)
        return recs[-n:] if n else recs

    @property
    def steps_total(self) -> int:
        """Step-log records emitted over the engine's lifetime (the ring
        keeps only the newest ``step_ring_size``)."""
        return self._step_id

    def _admit_from_queue(self) -> list[GenRequest]:
        """Strict-FIFO admission: pop the head only while a slot AND its
        whole (prefix-discounted) block reservation fit (head-of-line
        blocking = fairness).  Admitted requests join the prefill
        round-robin; their first token arrives when their last chunk
        completes."""
        admitted = []
        expired: list[GenRequest] = []
        with self._cond:
            while self._queue:
                head = self._queue[0]
                if head.t_deadline and time.time() > head.t_deadline:
                    # The caller's deadline passed while the request sat
                    # queued: abandon it NOW — decoding for a client that
                    # already timed out would only steal slots from live
                    # requests (overload turns into fast deadline errors
                    # instead of everything finishing late).
                    self._queue.popleft()
                    head.deadline_exceeded = True
                    head.error = (
                        f"deadline exceeded after "
                        f"{time.time() - head.t_submit:.3f}s in queue"
                    )
                    expired.append(head)
                    continue
                free = [i for i, r in enumerate(self._slots) if r is None]
                if not free:
                    break
                slot = free[0]
                pages = self.kv.admit(
                    slot,
                    self._footprint(len(head.prompt), head.max_new_tokens),
                    prompt=head.prompt if self.prefix_cache else None,
                )
                if pages is None:  # pool pressure (all-or-nothing rollback)
                    break
                self._queue.popleft()
                p = pages.prefix_tokens
                head.cached_prefix_tokens = p
                head.prefill_tokens = len(head.prompt) - p
                head.slot = slot
                head.status = "active"
                head.t_admit = time.time()
                head._t_attr = head.t_admit  # attribution frontier opens
                # chunked-prefill state: the grid stays anchored at 0, so
                # prefill starts at the last chunk boundary <= the first
                # uncached token (a straddling chunk re-writes the shared
                # tail with bitwise-identical K/V — see serve.kv_cache).
                head._fill_buf = np.zeros(
                    (self._padded_prompt_len(len(head.prompt)),), np.int32
                )
                head._fill_buf[: len(head.prompt)] = head.prompt
                head._fill_pad = len(head._fill_buf)
                head._fill_next = (p // self.prefill_chunk) \
                    * self.prefill_chunk
                self._slots[slot] = head
                self._slot_meta_dirty = True
                if self.fused_sampling:
                    # the request's sampling stream lives on device: one
                    # tiny scatter per admission, zero feeds per step
                    self._dev_keys = self._dev_keys.at[slot].set(
                        jax.random.PRNGKey(head.seed)
                    )
                self._filling.append(head)
                reused = self._slot_reused[slot]
                self._slot_reused[slot] = True
                self.counters["admits"] += 1
                if reused:
                    self.counters["admits_into_freed_slot"] += 1
                self._m_admits.inc(reused=str(reused).lower())
                if p:
                    self._m_prefix_hits.inc()
                    self._m_prefix_tokens.inc(p)
                self.counters["prefill_tokens"] += head.prefill_tokens
                self._m_prefill_tokens.inc(head.prefill_tokens)
                admitted.append(head)
            self._m_queue.set(len(self._queue))
        for req in expired:
            # Finished OUTSIDE the scheduler lock (log I/O, metrics).
            self._finish(req, None, status="error")
        self._stream_flush()
        self._m_active.set(sum(r is not None for r in self._slots))
        self._update_kv_metrics()
        return admitted

    def _filling_slots(self) -> int:
        """Requests admitted and still without a first token: those with
        chunks to run, and those whose last chunk was launched ahead."""
        pending = self._pending
        return len(self._filling) + (len(pending.finished) if pending else 0)

    def _launch_prefill(self, ahead: bool = False) -> _PrefillLaunch:
        """At most ``prefill_budget`` tokens of prefill chunks for one
        iteration, round-robin in budget-bounded BURSTS across the
        admitted-but-unfilled set: the head request runs consecutive
        chunks (its first token waits for its last chunk, so a budget
        spent on one filler brings a first token sooner than the same
        budget spread chunk by chunk over several) until
        it finishes or the budget runs out, then rotates to the back so
        the next iteration's budget goes to the next filler.  A long
        prompt can therefore neither starve decode (the per-iteration
        bound) nor monopolize prefill across iterations (the rotation).
        Always makes progress: at least one chunk runs when any request
        is filling, even with a budget below the chunk width.  Called
        only while a request is filling.

        In its own iteration (``engine.prefill``) a prompt's first token is
        sampled where its last chunk went, and the launch is accounted
        before it is returned.  ``ahead`` — under the decode step of the
        iteration before, whose tokens the host has not fetched — nothing
        here waits for the device: the chunks belong to slots that are not
        decoding, the head of ``_filling`` is the head the next iteration
        would pop (admission only appends), and the pools chain through
        the programs' donated arguments, so the device runs the chunks
        after that decode step as it would have.  The prompts that ended
        wait in ``finished`` for :meth:`_collect_prefill`."""
        budget = self.prefill_budget
        spent = 0
        launch = _PrefillLaunch(ahead=ahead)
        while self._filling and (budget is None or spent < budget):
            req = self._filling.popleft()
            done = False
            while True:
                last_logits = self._run_prefill_chunk(req, launch)
                spent += self.prefill_chunk
                if req._fill_next >= req._fill_pad:
                    if ahead:
                        launch.finished.append((req, last_logits))
                    else:
                        self._finish_prefill(req, last_logits)
                    done = True
                    break
                if budget is not None and spent >= budget:
                    break
            if not done:
                self._filling.append(req)
        if not ahead:
            self._account_prefill(launch)
        return launch

    def _collect_prefill(self, launch: _PrefillLaunch) -> None:
        """The iteration a launch was made ahead for is here: the first
        token of each prompt whose last chunk was among its chunks (the
        wait is for a chunk that has been running since before the last
        fetch), and the launch's place in the engine's counts."""
        for req, last_logits in launch.finished:
            self._finish_prefill(req, last_logits)
        launch.finished = []
        self._account_prefill(launch)

    def _account_prefill(self, launch: _PrefillLaunch) -> None:
        """``launch`` is this iteration's budget, spent: the engine's
        totals and the budget stall, in the iteration whose record holds
        its chunks."""
        self.prefill_iters += 1
        self.prefill_chunks += launch.chunks
        if launch.ahead:
            self.prefill_prelaunched += launch.chunks
        # budget stall: the token budget ran out with fillers still
        # pending — those requests eat >= 1 more iteration of TTFT (the
        # step-log field that explains a prefill-bound tail).  For a
        # launch made ahead this is asked after the admission it could
        # not see, as a launch in this iteration would have asked it.
        launch.stalled = bool(self._filling)
        if launch.stalled:
            self.prefill_budget_stalls += 1

    def _run_prefill_chunk(self, req: GenRequest, launch: _PrefillLaunch):
        """One fixed-width prefill chunk for one request: it reads the
        slot's earlier chunks through its page-table rows (and, over a state
        group, continues the state they left in the slot's own row), so
        chunks of several requests interleave freely."""
        slot = req.slot
        c = self.prefill_chunk
        start = req._fill_next
        # the leaf before this one (another chunk, a first token,
        # engine.admit; ahead: engine.decode.dispatch) ends here, with
        # whatever of the budget loop followed it
        chunk = self._tiles.to(
            "engine.decode" if launch.ahead else "engine.prefill",
            "engine.prefill_chunk")
        # Since this request's attribution frontier (the start of its
        # last chunk, or its admission): its own chunk's wall, read off
        # that chunk's span, was prefill compute; the rest was spent on
        # OTHER requests' work (their chunks, decode steps, admit scans)
        # — interference stall.
        t_chunk = time.time()
        interval = max(t_chunk - req._t_attr, 0.0)
        own = min(req._s_chunk.dur_s, interval) if req._s_chunk else 0.0
        req.attr_prefill_s += own
        req.attr_stall_s += interval - own
        req._t_attr = t_chunk
        req._s_chunk = chunk
        real = self._chunk_real_tokens(len(req.prompt), start)
        # (ahead, a window group maps and lets go of blocks of this slot's
        # own ring after the decode step in flight was handed its tables:
        # safe by the device's order, the chunk runs behind that step)
        self.kv.prepare_write(slot, start + c)
        last_logits, pools = self.programs.prefill(
            self.params, self.kv.pools(),
            req._fill_buf[start:start + c], start,
            {name: jnp.asarray(g.block_tables[slot].copy())
             for name, g in self.kv.groups.items()},
            real,
        )
        self.kv.set_pools(pools)
        launch.chunks += 1
        if self.kv.latent_layers:
            launch.context += start + c
            if (self.kv.index_topk and start + c > self.kv.index_topk
                    and self.programs.chunk_attention.startswith("masked_")):
                launch.select_walked += self.kv.latent_layers * select_walk(
                    start + c, self.kv.max_context)
        launch.tokens += real
        launch.pairs += real * start + real * (real + 1) // 2
        if self._summaries is not None:
            launch.summaries += int(self._summaries.rows_attended(start))
        req._fill_next = start + c
        self.kv.note_written(
            slot, max(min(start + c, len(req.prompt)),
                      int(self.kv.seq_lens[slot]))
        )
        return last_logits

    def _finish_prefill(self, req: GenRequest, last_logits) -> None:
        """The request's last chunk just completed: index its full prompt
        blocks (prefix cache), sample the first token (TTFT stops here),
        and hand the slot to the decode batch."""
        if self.prefix_cache:
            self.kv.register_prefix(req.slot, req.prompt)
        req._prefill_done = True
        self._slot_meta_dirty = True
        # the first-token sample blocks on the last chunk's logits: the
        # wait for the device and the host sampling are one span (the
        # request's bookkeeping and its stream's line below stay in it),
        # and the thread's CPU inside the wait is set aside (offcpu_s)
        self._tiles.to("engine.prefill", "engine.first_token")
        cpu0 = time.thread_time()
        if self.fused_sampling:
            # The prefill program hands logits to the host anyway
            # (its last chunk); sampling them with the device
            # sampler's exact math + key schedule (emitted index 0)
            # keeps the request on ONE sampling stream across the
            # host/device boundary.
            tok = sampling.sample_one(
                np.asarray(last_logits), jax.random.PRNGKey(req.seed),
                0, req.temperature, req.top_k,
            )
            self._dev_tokens = self._dev_tokens.at[req.slot, 0].set(tok)
        else:
            tok = self._sample(req, np.asarray(last_logits))
        self._cpu_blocked += time.thread_time() - cpu0
        req.t_first_token = time.time()
        req._t_last_token = req.t_first_token
        if self.startup_trace is not None:
            self._startup_mark("startup.first_chunk")
        # ... and the tail of this request's prefill compute in the
        # attribution ledger
        req.attr_prefill_s += max(req.t_first_token - req._t_attr, 0.0)
        req._t_attr = req.t_first_token
        req.tokens.append(tok)
        self._last_tokens[req.slot] = tok
        self._m_ttft.observe(req.ttft_s)
        if req.stream:
            self._stream_out.append((req, [tok], req.t_first_token))
        self._maybe_finish(req)
        self._stream_flush()    # the first token does not wait for a decode

    def _run_decode_step(self, prefill_s: float) -> None:
        """One decode iteration for every slot whose prefill is done:
        the one-token program (whose arg-max is a greedy slot's token; a
        slot that samples takes the numpy sampler on its row of the
        logits) or the fused fast path (sampling — and optionally
        speculative verification — inside the compiled program).  Both
        are three leaves that tile ``engine.decode``:
        ``engine.decode.dispatch``, open since ``engine.decode`` began
        (the batch's slots, CoW guard, slot meta, table upload, launch),
        then — only under a budget with a prompt filling — the
        ``engine.prefill_chunk`` leaves of the next iteration's prefill
        (:meth:`_to_fetch`),
        ``engine.decode.fetch`` (the wait
        for the device, and what the host needs of the result: a token a
        slot, and the logits only if a live request samples) and
        ``engine.decode.commit`` (host sampling where asked for, one pass
        of bookkeeping over the batch, then the streams' lines in one
        hand-over; it ends where ``engine.decode`` ends, so whatever the
        engine thread waits for after waking the stream writer is inside
        it).
        ``prefill_s`` is this iteration's ``engine.prefill`` wall, for the
        attribution split."""
        decoding = [
            (i, r) for i, r in enumerate(self._slots)
            if r is not None and r._prefill_done
        ]
        n_active = len(decoding)
        slots = np.fromiter((i for i, _ in decoding), np.intp, n_active)
        if self.fused_sampling:
            self._decode_step_fused(decoding, slots, prefill_s)
            return
        for i, _ in decoding:
            # CoW guard: never write a shared or indexed block in
            # place.  Steady state this is a no-op (appends land past
            # the shared prompt blocks) — it is what makes a future
            # scheduler bug a local copy instead of cross-request
            # cache corruption.
            self.kv.ensure_writable(i, int(self.kv.seq_lens[i]))
        self._refresh_slot_meta()
        for i, _ in decoding:
            self.kv.prepare_write(i, int(self.kv.seq_lens[i]) + 1)
        logits, greedy, pools, self._routed = self.programs.decode(
            self.params, self.kv.pools(),
            jnp.asarray(self._last_tokens), self._tables_dev(),
            self._seq_lens_dev(), self._dev_active,
        )
        self.kv.set_pools(pools)
        # what the engine sees in its input decides what it fetches: the
        # logits (slots x vocabulary floats) stay on the device unless a
        # live request samples from them
        sampling = [(j, r) for j, (_, r) in enumerate(decoding)
                    if r.temperature > 0.0]
        self._to_fetch()
        tokens = np.asarray(greedy)[slots]
        if sampling:
            logits = np.asarray(logits)
        now, decode_dt = self._to_commit()
        for j, req in sampling:
            tokens[j] = self._sample(req, logits[req.slot])
        self._note_sampled(n_active - len(sampling), bool(sampling))
        self.kv.note_written(slots, self.kv.seq_lens[slots] + 1)
        if self.kv.latent_layers:
            lens = self.kv.seq_lens[slots]
            read = scored = self.kv.latent_layers * int(lens.sum())
            if self.kv.index_topk:
                read = self.kv.latent_layers * int(
                    np.minimum(lens, self.kv.index_topk).sum())
                self._m_latent_read.inc(read)
                self._m_index_scored.inc(scored)
            self._step_latent = (read, scored)
            if self._walk_capacity:
                self._step_walk = self.kv.latent_layers * int(
                    (-(-lens // PAGED_LATENT_STRETCH)).sum())
        if self._paged_capacity or self._count_rows:
            positions = self.kv.seq_lens[slots] - 1    # the queries'
        if self._paged_capacity:
            self._step_paged_walk = sum(
                len(self.kv.layers[name]) * int(
                    (-(-end // PAGED_STRETCH) - first // PAGED_STRETCH).sum())
                for name, g in self.kv.paged.items()
                for first, end in [g.span_attended(positions)])
        if self._count_rows:
            for name, g in self.kv.paged.items():
                read = len(self.kv.layers[name]) * int(
                    g.rows_attended(positions).sum())
                self._step_rows_read[name] = read
                self._m_rows_read[name].inc(read)
        self._commit_tokens(
            decoding, slots, [[t] for t in tokens.tolist()], now,
            decode_dt, prefill_s, spec=False)

    def _to_fetch(self) -> None:
        """``engine.decode.dispatch`` ends and ``.fetch`` begins: the
        engine thread is about to wait for the device, so its CPU clock is
        read beside the span's wall (``_to_commit`` reads it again).

        Between the two, where a budget bounds an iteration's prefill and
        a prompt is still filling, the next iteration's prefill is launched
        behind the decode step (:meth:`_launch_prefill`, ``ahead``): the
        device has it to run while the host waits for this step's tokens,
        commits them, logs and admits.  An engine without a budget has no
        filler left here (every pending chunk ran before the decode step),
        so it never takes the branch."""
        if self.prefill_budget is not None and self._filling:
            self._pending = self._launch_prefill(ahead=True)
        self._tiles.to("engine.decode", "engine.decode.fetch")
        self._cpu_leaf0 = time.thread_time()

    def _to_commit(self) -> tuple[float, float]:
        """``.fetch`` ends and ``.commit`` begins, to last until
        ``engine.decode`` ends.  Returns the commit's stamp
        (``time.time()``: the requests' token times, and what a stream
        thread measures its line's lag from) and the wall of dispatch and
        fetch together, this iteration's share of a request's decode
        attribution."""
        tiles = self._tiles
        commit = tiles.to("engine.decode", "engine.decode.commit")
        cpu = time.thread_time()
        self._cpu_blocked += cpu - self._cpu_leaf0
        self._cpu_leaf0 = cpu
        return time.time(), commit.t0 - tiles.parent.t0

    def _commit_tokens(self, decoding, slots: np.ndarray,
                       kept: list[list[int]], now: float, decode_dt: float,
                       prefill_s: float, spec: bool) -> None:
        """The bookkeeping of one decode iteration, for the whole batch in
        one pass — ONE implementation for the one-token and fused paths,
        so telemetry (occupancy, tokens/step, ITL) cannot drift between
        them.  ``decoding`` is the iteration's ``(slot, request)`` pairs,
        ``slots`` the same slots as an array, ``kept`` the tokens each
        request commits (one, or a verified burst).

        Engine-wide counts and the histograms are taken once; a request's
        own fields are written in one loop that takes no lock and calls no
        method of the engine.  Each request's attribution frontier
        advances to ``now``, the interval split exclusively: this
        iteration's decode dispatch wall to decode (or, ``spec``, the
        speculative-verify component), up to this iteration's
        prefill-phase wall to interference stall (the engine ran other
        requests' chunks while this one had a token pending), the
        remainder to scheduler gap (admit scans, bookkeeping, idle waits
        between iterations) — worked out once for each frontier there is
        (one, but for requests whose prefill ended this iteration).  Last
        of all the streams' lines of this iteration go to ``stream_sink``
        in one call — one thread wakes for them, however many streams
        there are, and it runs while the engine waits for the next launch
        — then the requests that ended (EOS, length) are finished, and
        their ends follow in a call of their own."""
        n_active = len(decoding)
        self.decode_steps += 1
        self.counters["decode_dispatches"] += 1
        self.counters["slot_steps"] += n_active
        self._m_occ.observe(float(n_active))
        self.occupancy_max = max(self.occupancy_max, n_active)
        decode_dt, prefill_s = max(decode_dt, 0.0), max(prefill_s, 0.0)
        splits: dict[float, tuple[float, float, float]] = {}
        tokens = 0
        by_count: dict[int, int] = {}
        finished = []
        for (_, req), toks in zip(decoding, kept):
            split = splits.get(req._t_attr)
            if split is None:
                interval = max(now - req._t_attr, 0.0)
                d = min(interval, decode_dt)
                s = min(interval - d, prefill_s)
                split = splits[req._t_attr] = (d, s, interval - d - s)
            if spec:
                req.attr_spec_s += split[0]
            else:
                req.attr_decode_s += split[0]
            req.attr_stall_s += split[1]
            req.attr_gap_s += split[2]
            req._t_attr = now
            req.occ_sum += n_active
            req.occ_steps += 1
            if n_active > req.occ_max:
                req.occ_max = n_active
            req.tokens.extend(toks)
            n = len(toks)
            tokens += n
            by_count[n] = by_count.get(n, 0) + 1
            if req._t_last_token and now - req._t_last_token > req.itl_max_s:
                req.itl_max_s = now - req._t_last_token
            req._t_last_token = now
            if toks[-1] == req.eos_token_id \
                    or len(req.tokens) >= req.max_new_tokens:
                finished.append(req)
        self._last_tokens[slots] = [toks[-1] for toks in kept]
        self.counters["decode_tokens"] += tokens
        for n, requests in by_count.items():
            self._m_tok_step.observe(float(n), count=requests)
        self._stream_out += [(req, toks, now)
                             for (_, req), toks in zip(decoding, kept)
                             if req.stream]
        self._stream_flush()    # no line waits for the finishes
        for req in finished:
            self._maybe_finish(req)
        self._stream_flush()    # their ends

    def _decode_step_fused(self, decoding, slots: np.ndarray,
                           prefill_s: float) -> None:
        """One fused decode iteration: build the (optional) draft
        window, dispatch ONE program, commit the emitted bursts.

        The program returns ``(out_tokens, n_emitted, next_feed)`` —
        the only host transfer per iteration; ``next_feed`` stays on
        device as the next step's input.  Draft K/V is written for the
        whole window; the host commits only ``committed + accepted``
        positions (``kv.note_written``) so rejected-draft K/V is dead
        beyond the sequence length — and an EOS landing mid-burst
        truncates the request's tokens AND retreats the K/V extent
        (``kv.rollback``), which by construction never crosses a
        shared (refcount > 1) prefix block."""
        drafts: dict[int, list[int]] = {}
        if self.speculate:
            for i, r in decoding:
                cap = min(self.speculate,
                          r.max_new_tokens - len(r.tokens) - 1)
                if cap > 0:
                    # min_ngram=2: a single repeated token is mostly
                    # coincidence on novel text, and every spurious
                    # proposal pays the T=K+1 verify program for an
                    # almost-surely-rejected draft — requiring a 2-gram
                    # match keeps the low-hit-rate regression bounded
                    # while leaving real repetition (>= 2-gram) intact.
                    d = spec_draft.propose(
                        r.prompt + r.tokens, cap,
                        max_ngram=self.spec_ngram,
                        min_ngram=min(2, self.spec_ngram),
                    )
                    if d:
                        drafts[i] = d
        # Program choice is per BATCH: one drafting slot routes every
        # active slot through the T=K+1 program that iteration (static
        # shapes — the non-drafting slots' extra positions are pad
        # writes to scratch, but their forward compute still scales with
        # T).  The draft-less fallback therefore helps exactly when NO
        # slot drafts; a mixed batch pays the window for everyone, which
        # is the right trade only while acceptance is healthy — the
        # acceptance-rate telemetry is the dial to watch.
        t_width = self.speculate + 1 if drafts else 1
        for i, r in decoding:
            s = int(self.kv.seq_lens[i])
            self.kv.ensure_writable_range(
                i, s, s + 1 + len(drafts.get(i, ())))
        self._refresh_slot_meta()
        draft_lens = np.zeros((self.max_slots,), np.int32)
        if t_width > 1:
            toks = np.zeros((self.max_slots, t_width), np.int32)
            toks[:, 0] = self._last_tokens
            for i, d in drafts.items():
                toks[i, 1:1 + len(d)] = d
                draft_lens[i] = len(d)
            tokens_in = jnp.asarray(toks)
            dev_draft_lens = jnp.asarray(draft_lens)
            fn = self._fused_spec
        else:
            tokens_in = self._dev_tokens  # device-resident (B, 1) feed
            dev_draft_lens = self._dev_zero_drafts
            fn = self._fused1
        packed, next_feed, pools = fn(
            self.params, self.kv.pools(), tokens_in,
            dev_draft_lens, self._tables_dev(),
            self._seq_lens_dev(), self._dev_active,
            self._dev_keys, self._dev_prompt_lens, self._dev_temp,
            self._dev_topk,
        )
        self.kv.set_pools(pools)
        self._dev_tokens = next_feed
        self._to_fetch()
        packed = np.asarray(packed)  # the ONE small host fetch per
        out = packed[:, :-1]         # iteration (EOS / logging):
        n_emit = packed[:, -1]       # emitted tokens + counts, packed
        now, decode_dt = self._to_commit()
        self._note_sampled(len(decoding), False)
        seq0 = self.kv.seq_lens[slots]
        # Commit the last input token + every ACCEPTED draft's K/V
        # (emitted - 1 of them); rejected drafts' K/V sits past this
        # extent (dead, masked, overwritten by the next append).
        self.kv.note_written(slots, seq0 + n_emit[slots])
        bursts = []
        for (slot, req), s in zip(decoding, seq0.tolist()):
            n = int(n_emit[slot])
            emitted = [int(t) for t in out[slot, :n]]
            k_drafted = int(draft_lens[slot])
            accepted = n - 1
            kept = emitted
            if req.eos_token_id is not None \
                    and req.eos_token_id in emitted:
                kept = emitted[: emitted.index(req.eos_token_id) + 1]
                if len(kept) < n:
                    # tokens after the EOS never happened: retreat the
                    # K/V extent past the discarded accepted drafts too
                    self.kv.rollback(slot, s + len(kept))
            if k_drafted:
                # acceptance telemetry counts COMMITTED drafts: an
                # accepted draft discarded by the EOS truncation above
                # was rolled back as "never happened" and must not
                # inflate the acceptance rate.  kept == emitted keeps
                # `accepted`; a truncated burst is all-drafts.
                committed = accepted if len(kept) == n else len(kept)
                req.drafted += k_drafted
                req.accepted += committed
                self.counters["spec_drafted"] += k_drafted
                self.counters["spec_accepted"] += committed
                self._m_spec_drafted.inc(k_drafted)
                if committed:
                    self._m_spec_accepted.inc(committed)
            bursts.append(kept)
        # a T=K+1 (verify) dispatch charges the speculation
        # component for EVERY active slot — a mixed batch pays the
        # window for everyone, and the attribution should say so
        self._commit_tokens(decoding, slots, bursts, now, decode_dt,
                            prefill_s, spec=t_width > 1)

    def _note_sampled(self, device_sampled: int, logits_fetched: bool) -> None:
        """This decode iteration's ``device_sampled`` / ``logits_fetched``
        (step record) and their running totals: the slots whose token came
        off the device with the step — the program's arg-max for a greedy
        slot, every slot of a fused program — and whether the logits were
        fetched for the others."""
        self._step_sampled = (device_sampled, int(logits_fetched))
        self.counters["device_sampled_tokens"] += device_sampled
        self.counters["logit_fetches"] += int(logits_fetched)
        self.counters["host_sample_rounds"] += int(logits_fetched)

    def _sample(self, req: GenRequest, logits: np.ndarray) -> int:
        """Host-side sampler: the first token of every request (the prefill
        program hands its last row of logits to the host) and, in decode
        iterations, the requests with ``temperature > 0`` —
        greedy / temperature+top-k, deterministic per request seed.  The
        logits→probs math is the SHARED reference
        (:func:`serve.sampling.logits_to_probs`, fp32) — the historical
        float64 up-cast made this path drift from any fp32 device
        sampler in the last ulps, which poisoned parity testing."""
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        probs = sampling.logits_to_probs(
            np.asarray(logits), req.temperature, req.top_k, xp=np
        ).astype(np.float64)  # np.random requires probs summing to 1 in f64
        return int(req._rng.choice(len(probs), p=probs / probs.sum()))

    def _stream_flush(self) -> None:
        """Hand the lines gathered so far (and the ends: ``_finish``
        appends them) to ``stream_sink``: one call for all of them."""
        batch = self._stream_out
        if batch:
            self._stream_out = []
            if self.stream_sink is not None:
                self.stream_sink(batch)

    def _maybe_finish(self, req: GenRequest) -> None:
        last = req.tokens[-1]
        if req.eos_token_id is not None and last == req.eos_token_id:
            self._finish(req, "eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, "length")

    def _finish(self, req: GenRequest, reason: str,
                status: str = "ok") -> None:
        """Evict: release the slot's block references (registered prefix
        blocks park in the cached LRU, the rest free), close out metrics,
        signal."""
        if req.slot is not None:
            self.kv.release(req.slot)
            self._slots[req.slot] = None
            self._slot_meta_dirty = True
        if req in self._filling:  # error paths only; finished fills popped
            self._filling.remove(req)
        pending = self._pending
        if pending is not None and pending.finished:
            # it left between its last chunk's launch and its first token
            # (whatever takes its slot and blocks is queued behind the chunk)
            pending.finished = [f for f in pending.finished if f[0] is not req]
        req.status = status
        req.finish_reason = reason if status == "ok" else None
        req.t_done = time.time()
        if req._t_attr:
            # close the attribution ledger: the post-commit residue
            # (eviction bookkeeping) is scheduler gap, and the component
            # sum now equals e2e up to clock rounding
            req.attr_gap_s += max(req.t_done - req._t_attr, 0.0)
            req._t_attr = req.t_done
        self._step_evicted += 1
        self.counters[status] += 1
        self._m_requests.inc(status=status)
        if status == "ok":
            self.counters["tokens_generated"] += len(req.tokens)
            self._m_tokens.inc(len(req.tokens))
            self._m_e2e.observe(req.e2e_s)
            self._m_tpot.observe(req.tpot_s)
            self._emit_trace_spans(req)
        self._m_active.set(sum(r is not None for r in self._slots))
        self._update_kv_metrics()
        self._log_request(req)
        if req.stream:
            self._stream_out.append((req, None, req.t_done))
        req._done.set()

    def _update_kv_metrics(self) -> None:
        """Mirror the pool's host-side census into the obs registry
        (gauges set, monotonic kv counters bridged as deltas)."""
        alloc = self.kv.allocator
        self._m_blocks_free.set(alloc.free_blocks)
        self._m_blocks_cached.set(alloc.cached_blocks)
        self._m_block_refs.set(alloc.total_refs)
        if alloc.evictions > self._last_evictions:
            self._m_evictions.inc(alloc.evictions - self._last_evictions)
            self._last_evictions = alloc.evictions
        if self.kv.cow_copies > self._last_cow:
            self._m_cow.inc(self.kv.cow_copies - self._last_cow)
            self._last_cow = self.kv.cow_copies
        stats = self.kv.stats()
        self._m_frag.set(stats["fragmentation"])
        self._m_prefix_occ.set(stats["prefix_occupancy"])
        self._m_prefix_rate.set(stats["prefix_hit_rate"])

    def _emit_trace_spans(self, req: GenRequest) -> None:
        """Distributed request tracing: one root span per completed
        request plus its queue/prefill/decode phases, written to the
        active TraceRecorder's trace.jsonl under the request's trace_id
        (client-supplied via POST /generatez, so a slow request stitches
        against whatever upstream spans share the id).  Phase boundaries
        are the lifecycle stamps already taken — zero extra clock reads
        on the hot path; a no-op when no recorder is installed."""
        if obs_tracing.active_recorder() is None:
            return
        root = obs_tracing.new_span_id()
        obs_tracing.record_remote_span(
            "serve.request", t0=req.t_submit, dur_s=req.e2e_s,
            trace_id=req.trace_id, span_id=root, request=req.id,
            prompt_tokens=len(req.prompt), new_tokens=len(req.tokens),
            cached_prefix_tokens=req.cached_prefix_tokens,
        )
        obs_tracing.record_remote_span(
            "serve.queue", t0=req.t_submit,
            dur_s=max(req.t_admit - req.t_submit, 0.0),
            trace_id=req.trace_id, parent_id=root, request=req.id,
        )
        obs_tracing.record_remote_span(
            "serve.prefill", t0=req.t_admit,
            dur_s=max(req.t_first_token - req.t_admit, 0.0),
            trace_id=req.trace_id, parent_id=root, request=req.id,
            slot=req.slot if req.slot is not None else -1,
        )
        if len(req.tokens) > 1:
            obs_tracing.record_remote_span(
                "serve.decode", t0=req.t_first_token,
                dur_s=max(req.t_done - req.t_first_token, 0.0),
                trace_id=req.trace_id, parent_id=root, request=req.id,
                tokens=len(req.tokens),
            )

    # -- loop / lifecycle ----------------------------------------------------

    def start(self) -> "Engine":
        if self._stopped or self._crashed is not None:
            # A stopped/crashed engine holds closed log handles and failed
            # requests — relaunching its loop would only busy-wait while
            # submit() refuses everything.  Build a fresh Engine instead.
            raise RuntimeError("engine cannot be restarted after stop()")
        if self._thread is None:
            self._stop_flag = False
            self._thread = threading.Thread(
                target=self._run, name="dtf-serve-engine", daemon=True
            )
            self._thread.start()
        return self

    @property
    def healthy(self) -> bool:
        """False once the scheduler loop has died or been stopped
        (surfaced as a 503 on ``/healthz`` so a balancer stops routing
        to this process)."""
        return self._crashed is None and not self._stopped

    def _run(self) -> None:
        """The engine thread: ``engine.step`` and, around it,
        ``engine.loop`` — what the loop does between two iterations (the
        capture engine's window by iteration, the scheduler lock, the
        stop flag) with the idle ``Condition.wait`` inside it as
        ``engine.wait`` — so the roots leave nothing of the thread's life
        unnamed."""
        cap = self.capture
        span = obs_tracing.span
        if cap is not None:
            cap.maybe_start(self._step_id)
        while True:
            try:
                did = self.step()
                with span("engine.loop"):
                    if cap is not None:
                        cap.maybe_stop(self._step_id)
                    with self._cond:
                        if self._stop_flag:
                            return
                        if not did and not self._queue:
                            with span("engine.wait") as s_wait:
                                cpu0 = time.thread_time()
                                self._cond.wait(timeout=0.05)
                                self._cpu_blocked += time.thread_time() - cpu0
                            self._wait_s += s_wait.dur_s
                    if cap is not None:
                        cap.maybe_start(self._step_id)
            except Exception as e:  # noqa: BLE001 — fail every in-flight req
                self._crashed = repr(e)
                self._fail_all(f"engine loop error: {e!r}")
                raise

    def stop(self, drain: bool = True, timeout: float = 60.0) -> None:
        """Stop the loop.  ``drain=True`` (default) finishes in-flight and
        queued requests first; ``drain=False`` errors them out."""
        if self._thread is not None:
            if drain:
                deadline = time.time() + timeout
                while time.time() < deadline:
                    with self._cond:
                        idle = not self._queue and all(
                            r is None for r in self._slots
                        )
                    if idle:
                        break
                    time.sleep(0.01)
            with self._cond:
                self._stop_flag = True
                self._cond.notify_all()
            self._thread.join(timeout=timeout)
            self._thread = None
        if self.capture is not None:
            self.capture.abort(self._step_id)  # close a still-open window
        self._stopped = True
        self._fail_all("engine stopped")
        self._log_metrics_row()
        with self._log_lock:
            # Closed under the log lock: an HTTP thread mid-_log_request
            # (a late 429) must never hit a closed/None file handle.
            if self._req_log is not None:
                self._req_log.close()
                self._req_log = None
            if self._met_log is not None:
                self._met_log.close()
                self._met_log = None
            if self._step_log is not None:
                self._step_log.close()
                self._step_log = None
        if self.logdir:
            self._registry.write_prometheus(
                os.path.join(self.logdir, "metrics.prom")
            )

    def __enter__(self) -> "Engine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _fail_all(self, message: str) -> None:
        with self._cond:
            doomed = list(self._queue)
            self._queue.clear()
            self._m_queue.set(0)
        self._filling.clear()  # entries are also in _slots, failed below
        self._pending = None   # nobody is left to collect it
        doomed += [r for r in self._slots if r is not None]
        for req in doomed:
            req.error = message
            self._finish(req, "error", status="error")
        self._stream_flush()

    # -- introspection / logs ------------------------------------------------

    def state(self) -> dict:
        """JSON-safe engine state for ``GET /generatez``."""
        with self._lock:
            queue_depth = len(self._queue)
        slots = [
            None if r is None else {
                "id": r.id, "tenant": r.tenant,
                "seq_len": int(self.kv.seq_lens[i]),
                "new_tokens": len(r.tokens),
                "max_new_tokens": r.max_new_tokens,
                "phase": "decode" if r._prefill_done else "prefill",
                "cached_prefix_tokens": r.cached_prefix_tokens,
            }
            for i, r in enumerate(self._slots)
        ]
        return {
            "queue_depth": queue_depth,
            "max_queue": self.max_queue,
            "max_slots": self.max_slots,
            "active_slots": sum(s is not None for s in slots),
            "filling_slots": sum(
                s is not None and s["phase"] == "prefill" for s in slots
            ),
            "slots": slots,
            "decode_steps": self.decode_steps,
            "occupancy_max": self.occupancy_max,
            "prefill_iters": self.prefill_iters,
            "prefill_chunks": self.prefill_chunks,
            "prefill_prelaunched": self.prefill_prelaunched,
            "prefill_budget_stalls": self.prefill_budget_stalls,
            "steps_total": self._step_id,
            "step_ring_size": self.step_ring_size,
            # programs JAX compiled or loaded inside an iteration: how
            # many, their seconds, the last one's name and when
            "compiles": dict(self._compiles),
            "kv": self.kv.stats(),
            "counters": dict(self.counters),
            "prefill_chunk": self.prefill_chunk,
            "prefill_budget": self.prefill_budget or 0,
            "prefix_cache": self.prefix_cache,
            "fused_sampling": self.fused_sampling,
            "speculate": self.speculate,
            # "paged_attn", "paged_latent_attn" or "plain" (serve.model):
            # the fallback is silent
            "decode_attention": self.programs.decode_attention,
            # the same of a prefill chunk: "latent_chunk_attn" or "plain"
            "chunk_attention": self.programs.chunk_attention,
            # the form a prefill chunk scans a state group's layers with:
            # "ssm_chunk_scan" or "plain"; None where no layer keeps a state
            "chunk_scan": self.programs.chunk_scan,
            # what a state layer keeps a slot: "conv_tail+scan_state"
            # (jamba), "conv_tail" (lfm2); None where no layer keeps a state
            "state_form": self.programs.state_form,
            # bytes the cache stores a token over all layers
            "cache_row_bytes": self.kv.row_bytes,
            # the layer slots a token keeps rows in: the paged groups'
            # layers, times the passes of a stack that is run several times
            "cache_layer_slots": self.kv.layer_slots,
            "kv_groups": self.kv_groups(),
            "spec_acceptance_rate": (
                self.counters["spec_accepted"] / self.counters["spec_drafted"]
                if self.counters["spec_drafted"] else 0.0
            ),
            "tokens_per_step": (
                self.counters["decode_tokens"] / self.counters["slot_steps"]
                if self.counters["slot_steps"] else 0.0
            ),
            "max_context": self.kv.max_context,
        }

    def kv_groups(self) -> dict:
        """``{paged group: what it stores a token a layer (its form, K/V
        heads, bytes of values and as laid out) and the formulations the
        one-token program and a prefill chunk attend its pages with}``: the
        ``startup.engine_build`` row's and ``state()``'s."""
        forms = self.programs.formulations
        return {name: {"layers": len(self.kv.layers[name]), **g.census,
                       **forms[name]}
                for name, g in self.kv.paged.items()}

    def _log_request(self, req: GenRequest) -> None:
        row = {
            "id": req.id,
            "status": req.status,
            "prompt_tokens": len(req.prompt),
            "new_tokens": len(req.tokens),
            "trace_id": req.trace_id,
            "tenant": req.tenant,
        }
        if req.status == "ok":
            row.update(
                finish_reason=req.finish_reason,
                ttft_s=round(req.ttft_s, 6),
                tpot_s=round(req.tpot_s, 6),
                e2e_s=round(req.e2e_s, 6),
                queue_s=round(max(req.t_admit - req.t_submit, 0.0), 6),
                slot=req.slot if req.slot is not None else -1,
                occ_mean=(round(req.occ_sum / req.occ_steps, 3)
                          if req.occ_steps else 0.0),
                occ_max=req.occ_max,
                cached_prefix_tokens=req.cached_prefix_tokens,
                prefill_tokens=req.prefill_tokens,
                itl_max_s=round(req.itl_max_s, 6),
                drafted=req.drafted,
                accepted=req.accepted,
                # per-request speculative split under the fleet-wide
                # spelling (the global counters' names), next to the
                # legacy drafted/accepted pair
                spec_drafted=req.drafted,
                spec_accepted=req.accepted,
                # exclusive tail-latency attribution: queue + prefill +
                # stall + decode + spec + gap == e2e up to rounding
                # (tools/tail_report.py joins these against steps.jsonl)
                attr_queue_s=round(max(req.t_admit - req.t_submit, 0.0), 6),
                attr_prefill_s=round(req.attr_prefill_s, 6),
                attr_stall_s=round(req.attr_stall_s, 6),
                attr_decode_s=round(req.attr_decode_s, 6),
                attr_spec_s=round(req.attr_spec_s, 6),
                attr_gap_s=round(req.attr_gap_s, 6),
            )
        elif req.error:
            row["error"] = req.error
        with self._log_lock:
            # t stamped under the lock so the stream stays time-ordered
            # across the engine + HTTP threads (schema checker invariant);
            # the handle re-checked under it so stop() can't close the
            # file out from under a late writer.
            if self._req_log is None:
                return
            row = {"t": time.time(), **row}
            self._req_log.write(json.dumps(json_sanitize(row)) + "\n")
            self._req_log.flush()

    def _log_metrics_row(self) -> None:
        kv = self.kv.stats()
        row = {
            "step": self.decode_steps,
            "queue_depth": len(self._queue),
            "active_slots": sum(r is not None for r in self._slots),
            "filling_slots": self._filling_slots(),
            "occupancy_max": self.occupancy_max,
            "blocks_free": kv["blocks_free"],
            "blocks_cached": kv["blocks_cached"],
            "block_refs": kv["block_refs"],
            "kv_fragmentation": round(kv["fragmentation"], 4),
            "prefix_occupancy": round(kv["prefix_occupancy"], 4),
            "prefix_hit_rate": round(kv["prefix_hit_rate"], 4),
            "prefix_lookups_total": kv["prefix_lookups"],
            "prefix_hits_total": kv["prefix_hits"],
            "prefix_cached_tokens_total": kv["prefix_cached_tokens"],
            "prefill_tokens_total": self.counters["prefill_tokens"],
            "prefix_evictions_total": kv["prefix_evictions"],
            "cow_copies_total": kv["cow_copies"],
            "prefill_iters": self.prefill_iters,
            "prefill_chunks": self.prefill_chunks,
            "prefill_prelaunched": self.prefill_prelaunched,
            "prefill_chunk": self.prefill_chunk,
            "prefill_budget": self.prefill_budget or 0,
            "requests_ok_total": self.counters["ok"],
            "requests_rejected_total": self.counters["rejected"],
            "requests_error_total": self.counters["error"],
            "tokens_generated_total": self.counters["tokens_generated"],
            # decode fast path (ISSUE 15)
            "fused_sampling": int(self.fused_sampling),
            "speculate": self.speculate,
            "spec_drafted_total": self.counters["spec_drafted"],
            "spec_accepted_total": self.counters["spec_accepted"],
            "spec_acceptance_rate": round(
                self.counters["spec_accepted"]
                / self.counters["spec_drafted"], 4
            ) if self.counters["spec_drafted"] else 0.0,
            "decode_tokens_total": self.counters["decode_tokens"],
            # PER-SLOT (decode_tokens over slot-steps): 1.0 without
            # speculation, up to speculate+1 — the scalar twin of the
            # serve_decode_tokens_per_step histogram.
            "tokens_per_step": round(
                self.counters["decode_tokens"] / self.counters["slot_steps"],
                4,
            ) if self.counters["slot_steps"] else 0.0,
            "decode_dispatches_total": self.counters["decode_dispatches"],
            "host_sample_rounds_total": self.counters["host_sample_rounds"],
        }
        with self._log_lock:
            if self._met_log is None:
                return
            self._met_log.write(json.dumps(json_sanitize(row)) + "\n")
            self._met_log.flush()
        if self.logdir:
            self._registry.write_prometheus(
                os.path.join(self.logdir, "metrics.prom")
            )
