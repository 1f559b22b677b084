"""Continuous-batching inference engine.

The orchestrator the reference delegates to vLLM/JetStream (reference
llm/vllm example YAMLs; SURVEY.md §2.6 — serving is GPU-delegated there).
TPU-first structure:

- All device work is TWO compiled programs: ``prefill`` (per prompt
  bucket) and ``decode+sample`` (one token for every slot, fused). Static
  shapes everywhere; slot refill never recompiles.
- The KV cache is donated through the decode step, so XLA updates it in
  place in HBM (no copy of the multi-GB cache per token).
- Decode crosses the host boundary as [slots] int32 — sampling happens
  on-device (``sampling.py``).
- Prompt lengths are bucketed (powers of two) to bound prefill
  compilations.
- The step loop is OVERLAPPED (``pipeline_depth``): decode N+1 is
  dispatched before step N's pair is read back (it depends only on the
  device-resident last-token vector and cache), host bookkeeping runs
  one step behind the device, and per-token operands (temps, active
  mask, block table) live on device behind dirty flags instead of
  being re-uploaded every token (docs/serving.md, "The decode
  pipeline"). A prompt's FIRST token does not wait for a pair: the
  chunk that ends the prompt returns it, and its record is read as
  soon as that chunk has ended, ahead of the decode dispatched behind
  it (``infer/inflight.py``: the in-flight records and the consume
  ladder).
- Token delivery is event-driven: every consumed token fires the
  request's condition/listeners (``Request.wait_progress``), so the
  server streams without sleep-polling.

Metrics: per-request TTFT (submit → first token on host) and decode
throughput, surfaced by ``metrics()`` for the serve layer's p50-TTFT
target (BASELINE.md).
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.infer import cache as cache_lib
from skypilot_tpu.infer import drafter as drafter_lib
from skypilot_tpu.infer import inflight
from skypilot_tpu.infer import kv_wire
from skypilot_tpu.infer import model as model_lib
from skypilot_tpu.infer import paged_cache as paged_cache_lib
from skypilot_tpu.infer import prefix_cache as prefix_cache_lib
from skypilot_tpu.infer import sampling as sampling_lib
from skypilot_tpu.infer import sched as sched_lib
from skypilot_tpu.models import interface
from skypilot_tpu.models import llama
from skypilot_tpu.observability import stepline as stepline_lib
from skypilot_tpu.utils import failpoints
from skypilot_tpu.utils import prefix_hash

# Back-compat re-export: admission control moved into the scheduler
# subsystem (infer/sched/), but the server and the lockstep driver
# catch it by this name.
AdmissionError = sched_lib.AdmissionError


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 8
    max_seq_len: int = 2048
    prefill_buckets: Sequence[int] = (16, 64, 256)
    eos_id: Optional[int] = None
    max_new_tokens: int = 256
    top_k: int = 0
    cache_dtype: str = 'bfloat16'
    # Dispatch-ahead decode (the overlapped pipeline): up to this many
    # decode steps may be in flight on the device before the host reads
    # a result back, so host bookkeeping (finish checks, slot refill,
    # page accounting) overlaps device compute instead of serializing
    # with it. Host state runs stale-by-depth: a slot that finished at
    # step N still decodes at N+1 (its token is dropped at consume) and
    # is masked out at N+2. 0 = today's fully synchronous loop — the
    # multihost lockstep driver pins 0 until its tick protocol learns
    # overlap. Greedy outputs are bit-identical at any depth (sampling
    # at temperature 0 is argmax, key-free; page-pressure decisions
    # drain the in-flight queue before acting).
    pipeline_depth: int = 1
    # Tensor-parallel degree: shard params (Megatron-style, the
    # column/row rules in parallel/sharding.py) and the KV cache (over
    # KV heads) across the first `tp` local devices. An 8B model in bf16
    # does not fit one v5e chip; tp=4/8 over ICI makes it servable —
    # GSPMD inserts the all-reduces, the engine code is unchanged.
    tp: int = 1
    # Chunked prefill (the round-3 TTFT-under-concurrency fix): prompts
    # are processed in <=prefill_chunk-token chunks interleaved with
    # decode steps, so a long prompt never head-of-line blocks every
    # active slot's next token. chunks_per_step bounds prefill work per
    # engine step.
    prefill_chunk: int = 256
    prefill_chunks_per_step: int = 4
    # Paged only: a prompt's last chunk pads to the smallest of a ladder
    # of buckets under the chunk (page, 2 x page, ...), one compiled
    # prefill program a bucket. False: every chunk pads to the chunk
    # itself, ONE prefill program. For a model whose chunk program is
    # dear to compile and whose prompts are many chunks long: half a
    # chunk of padding a prompt against a start-up of one program, not
    # one a bucket.
    prefill_tail_buckets: bool = True
    # Fused mixed steps (docs/serving.md "Fused mixed steps"): while
    # any slot is decoding, ONE prefill chunk rides the decode
    # dispatch as a single fused device program (model.mixed_step /
    # paged_mixed_step) instead of a standalone prefill dispatch
    # landing BETWEEN decode dispatches — the decode batch's
    # inter-token latency stops absorbing whole prefill chunks under
    # long-prompt admissions, and each layer's weights stream once
    # for chunk + decode combined. The scheduler's chunk-budget hook
    # (Scheduler.next_prefill_slot) picks which prefilling slot gets
    # the fused lane. Greedy outputs are BIT-IDENTICAL fused on vs
    # off (dense+paged, any pipeline depth, spec on/off); only step
    # timing changes. Off by default (the historical step shape).
    fused_prefill: bool = False
    # int8 weight-only quantization (ops/quant.py): halves weight HBM
    # bytes (8B fits one v5e chip) and speeds the bandwidth-bound decode.
    quantize: bool = False
    # Paged KV cache (infer/paged_cache.py + ops/paged_attention.py):
    # slots share a pool of fixed-size pages, HBM ∝ tokens-in-flight
    # instead of slots x max_seq_len, and one engine serves mixed
    # 2k/16k prompts (subsumes the round-4 two-tier EnginePool). When
    # the pool runs dry mid-decode, the youngest other slot is
    # preempted and resumed later by re-prefilling prompt+output.
    paged: bool = False
    page_size: int = 64
    # KV page value dtype (paged only): 'bfloat16' (default — the
    # cache_dtype path, bit-for-bit the pre-quantization engine) or
    # 'int8' — pages hold int8 values plus one fp32 absmax scale per
    # token row per KV head (quant-on-write, dequant-in-kernel;
    # ops/paged_attention.py), halving KV bytes per token so the same
    # HBM budget holds ~2x the resident pages (bigger prefix cache,
    # less preemption). Greedy outputs are NOT bit-identical to bf16 —
    # they are gated at a pinned tolerance (max logit delta + a
    # greedy-divergence-step floor, tests/unit_tests/test_infer_fused.py).
    kv_dtype: str = 'bfloat16'
    # Total pool pages (page 0 is a reserved garbage sink). None →
    # dense-equivalent capacity (n_slots * max_seq_len / page_size + 1);
    # set lower to cap KV HBM at the expected tokens-in-flight.
    n_pages: Optional[int] = None
    # Shared-prefix KV reuse (infer/prefix_cache.py, requires paged):
    # finished/preempted requests donate their full clean pages to a
    # radix tree keyed by per-page token blocks; a new request attaches
    # the longest cached page-aligned prefix of its prompt (refcount++)
    # and prefills only from the match boundary. Unreferenced cached
    # pages are LRU-evicted strictly under page pressure, before
    # preemption is considered. Greedy outputs are bit-identical with
    # the cache on vs off (same determinism bar as pipeline_depth).
    prefix_cache: bool = False
    # Admission control (docs/robustness.md "Zero-downtime serving"):
    # bound the waiting queue so a saturated engine sheds load (the
    # server answers 429 + Retry-After) instead of queueing without
    # bound. None = unbounded. max_queue_tokens caps the total
    # prompt+resume tokens parked in the queue — the companion knob for
    # few-but-huge prompts. Under 'wfq' these bounds are split into
    # per-tenant quotas by weight.
    max_queue_requests: Optional[int] = None
    max_queue_tokens: Optional[int] = None
    # Self-speculative decoding (docs/serving.md "Speculative
    # decoding"): a host-side prompt-lookup drafter (infer/drafter.py)
    # proposes up to spec_k candidate tokens per greedy slot and ONE
    # fused `verify` program scores every candidate in a single device
    # step (static draft length via padding + a per-slot draft_len
    # mask, like the prefill buckets); the engine accepts the longest
    # exact-greedy-matching prefix plus one corrected token, so a step
    # emits 1..spec_k+1 tokens per slot while greedy outputs stay
    # BIT-IDENTICAL to spec_k=0 (every emitted token is the model's
    # own argmax — drafts only decide how many land per step). 0 = off
    # (the default; sampled slots always decode token-at-a-time, and
    # the multihost lockstep driver pins 0 — the tick spec does not
    # carry draft tokens). The scheduler can narrow a request's draft
    # width per step (Scheduler.spec_budget: wfq caps an over-share
    # tenant under contention).
    spec_k: int = 0
    # Longest trailing n-gram the drafter matches (falls back to
    # shorter grams down to 1).
    spec_ngram: int = 3
    # Step-loop scheduling policy (infer/sched/, docs/serving.md
    # "Engine scheduler"): 'fcfs' (default — bit-identical to the
    # historical inline behavior), 'deadline' (EDF over wall-clock
    # budgets), 'wfq' (per-tenant weighted fair queueing).
    scheduler: str = 'fcfs'
    # Flight recorder (observability/stepline.py, docs/observability.md
    # "Flight recorder"): an always-on ring of per-step records
    # (stage wall-time shares, batch/chunk sizes, speculation accepts,
    # page pressure, per-tenant queue depth) plus per-request timeline
    # events, surfaced at GET /debug/stepline and snapshotted into the
    # span store on anomalies. Pure observation: it reads clocks and
    # counters, never scheduling state the step loop acts on.
    # Ring capacity in step records (None -> SKY_TPU_STEPLINE_CAP or
    # 1024); the request-event ring holds 4x as many.
    stepline_cap: Optional[int] = None
    # TTFT SLO in seconds: a request whose first token lands slower
    # than this triggers an anomaly dump (the ring snapshots into the
    # span store, read later with `sky-tpu profile`). None = no SLO
    # trigger.
    ttft_slo_s: Optional[float] = None
    # tenant -> relative weight for 'wfq' (unknown tenants weigh 1.0).
    # A mapping in a frozen dataclass: treat as immutable.
    tenant_weights: Optional[Any] = None
    # On-device SDC sentinel (docs/robustness.md "Data integrity"): a
    # jnp.isfinite reduction over each step's logits rides the
    # existing readback pair as one extra int32 row — no extra
    # device->host transfer, no new compiled programs (the flag is a
    # trace-time branch inside the SAME pinned program set). A NaN/inf
    # hit finishes the slot with reason 'sdc', marks the engine
    # integrity_suspect (one-way; /health flips to 503 "corrupt") and
    # fires an 'sdc' stepline anomaly dump. Greedy outputs and
    # decode_steps are BIT-IDENTICAL sentinel on vs off — the row is
    # appended after the token rows, so every consume index is
    # unchanged.
    sdc_sentinel: bool = True


@dataclasses.dataclass
class Request:
    request_id: int
    prompt_tokens: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    output_tokens: List[int] = dataclasses.field(default_factory=list)
    submitted_at: float = dataclasses.field(default_factory=time.time)
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    finish_reason: Optional[str] = None
    # Multi-tenant identity (X-SkyTpu-Tenant end to end): the unit of
    # fair queueing, quotas, and the per-tenant metric breakdown.
    tenant: str = sched_lib.DEFAULT_TENANT
    # When the engine dispatched this request's FIRST prefill chunk —
    # the boundary that decomposes TTFT into queue wait (submit →
    # first dispatch, the scheduler's doing) vs prefill compute
    # (dispatch → first token). Not re-stamped on preemption resume.
    first_dispatch_at: Optional[float] = None
    # Prompt tokens served from the shared-prefix cache (their prefill
    # was skipped); surfaced per request by the server's done-line.
    cached_tokens: int = 0
    # Tokens this request resumed from (mid-stream failover: the serve
    # LB re-issues a died stream with the already-delivered tokens as
    # ``resume_from``). They are pre-seeded into output_tokens and
    # prefilled with the prompt; the server stream never re-emits them.
    resumed_from: int = 0
    # Wall-clock deadline (absolute time.time()): once passed, the
    # engine finishes the request ('deadline') at its next step —
    # queued or decoding — and frees its slot/pages. None = no deadline.
    deadline: Optional[float] = None
    # Cooperative cancellation (client disconnect): flagged by
    # ``InferenceEngine.cancel``; only the engine thread acts on it
    # (queued → dropped before admission, active → finished
    # 'cancelled'), so device state is never touched from HTTP threads.
    cancelled: bool = False
    # Per-request speculation opt-out (body {"spec": false}): the
    # request is never drafted for — it emits one token per step (it
    # may still co-ride another slot's verify dispatch as a
    # draft_len=0 lane, which is compute-identical to decode for it).
    # Outputs are bit-identical either way; only step count differs.
    spec: bool = True
    # Verify-step accounting (engine thread only): steps this request
    # rode a verify dispatch, and tokens those steps emitted for it —
    # the per-request accepted_len_mean on the /generate done-line.
    spec_steps: int = 0
    spec_emitted: int = 0
    # Prompt-lookup drafter memo (incremental n-gram index over
    # prompt+output; engine thread only — survives slot moves and
    # preemptions with the request).
    draft_memo: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # Token-event delivery: the engine notifies after every appended
    # token and on finish, so consumers (HTTP handlers, the lockstep
    # warm-up) wait on the condition instead of sleep-polling the
    # output list at a 2-5 ms cadence.
    _cond: threading.Condition = dataclasses.field(
        default_factory=threading.Condition, repr=False, compare=False)
    _listeners: List[Any] = dataclasses.field(
        default_factory=list, repr=False, compare=False)

    @property
    def done(self) -> bool:
        return self.finished_at is not None

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def queue_wait(self) -> Optional[float]:
        """Seconds from submit to the first prefill-chunk dispatch —
        the scheduling (not compute) share of TTFT."""
        if self.first_dispatch_at is None:
            return None
        return self.first_dispatch_at - self.submitted_at

    # ---- token events ----------------------------------------------------
    def add_listener(self, callback) -> None:
        """Register a zero-arg callable fired (from the engine thread)
        on every appended token and on finish — the asyncio bridge for
        event-driven streaming (server._TokenWaiter)."""
        self._listeners.append(callback)

    def remove_listener(self, callback) -> None:
        try:
            self._listeners.remove(callback)
        except ValueError:
            pass

    def _notify(self) -> None:
        with self._cond:
            self._cond.notify_all()
        for cb in tuple(self._listeners):
            try:
                cb()
            except Exception:  # noqa: BLE001 — a dying waiter (closed
                pass           # event loop) must not wedge the engine

    def wait_progress(self, n_seen: int,
                      timeout: Optional[float] = None) -> bool:
        """Block until more than ``n_seen`` tokens exist or the request
        finishes. Returns whether there is progress to read."""
        with self._cond:
            return self._cond.wait_for(
                lambda: len(self.output_tokens) > n_seen or self.done,
                timeout)

    def wait_done(self, timeout: Optional[float] = None) -> bool:
        with self._cond:
            return self._cond.wait_for(lambda: self.done, timeout)


@dataclasses.dataclass
class _ChunkPlan:
    """A prepared-but-not-yet-dispatched prefill chunk: page coverage
    secured, bucket chosen, tokens padded. Dispatches either standalone
    (``_dispatch_chunk_plan``) or fused into the decode dispatch
    (``_dispatch_mixed``). Engine thread only."""
    slot: int
    req: Request
    off: int           # prefill offset this chunk starts at
    bucket: int        # padded chunk length (compiled shape)
    tl: int            # valid tokens in the chunk
    total: int         # prompt+resume tokens the slot must cache
    padded: 'np.ndarray'
    table_row: Optional[Any] = None   # paged: slot's block-table row


def tp_mesh(tp: int) -> 'jax.sharding.Mesh':
    """The engine's tensor-parallel mesh ((tp, fsdp=1) so the training
    param rules apply directly).

    Single-process: the first `tp` local devices. Multi-process
    (multi-host replica): `tp` devices striped EVENLY across processes —
    every process must own part of the mesh, or the non-participating
    hosts execute programs whose outputs they cannot address (and the
    participating host does all the work)."""
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < tp:
        raise ValueError(f'tp={tp} but only {len(devs)} devices')
    nproc = jax.process_count()
    if nproc > 1:
        if tp % nproc:
            raise ValueError(
                f'multi-host replica: tp={tp} must be a multiple of '
                f'the process count ({nproc}) so every host owns an '
                f'equal part of the mesh')
        per = tp // nproc
        by_proc: dict = {}
        for d in devs:
            by_proc.setdefault(d.process_index, []).append(d)
        short = [p for p, ds in by_proc.items() if len(ds) < per]
        if short:
            raise ValueError(
                f'tp={tp} needs {per} devices per process; processes '
                f'{short} have fewer')
        chosen = [d for p in sorted(by_proc)
                  for d in by_proc[p][:per]]
    else:
        chosen = devs[:tp]
    return Mesh(np.array(chosen).reshape(tp, 1), ('tp', 'fsdp'))


def init_params_sharded(config: llama.LlamaConfig, tp: int,
                        seed: int = 0) -> llama.Params:
    """Initialize params DIRECTLY onto the tp mesh — an 8B model cannot
    first materialize on one chip (jit with out_shardings makes XLA
    produce each shard on its own device)."""
    from skypilot_tpu.parallel import sharding as sharding_lib
    mesh = tp_mesh(tp)
    init = lambda: llama.init_params(config, jax.random.PRNGKey(seed))  # noqa: E731
    shardings = sharding_lib.param_shardings(mesh, jax.eval_shape(init))
    return jax.jit(init, out_shardings=shardings)()


class _KVJob:
    """One queued KV transfer operation (export or import).

    Any thread may enqueue (request_kv_export / request_kv_import);
    only the STEPPING thread services — the radix tree and page pool
    are engine-thread-confined, so the job queue is how the HTTP
    handlers borrow the owner thread instead of racing it. The waiter
    blocks on the event (the server does so via asyncio.to_thread, off
    the event loop)."""

    def __init__(self, kind: str, payload: Any,
                 fetch_s: float = 0.0) -> None:
        self.kind = kind          # 'export' | 'import'
        self.payload = payload    # export: token list; import: blob
        self.fetch_s = fetch_s    # import: upstream fetch wall time
        self.result: Any = None
        self.error: Optional[Exception] = None
        self._done = threading.Event()

    def finish(self, result: Any = None,
               error: Optional[Exception] = None) -> None:
        self.result, self.error = result, error
        self._done.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)


class InferenceEngine(inflight.ConsumeLadder):
    """Slot-based continuous batching over one model replica."""

    # Concurrency contract, enforced statically by `sky-tpu lint`
    # (SKY-LOCK, docs/static-analysis.md). HTTP handler threads call
    # submit()/cancel()/metrics(); the engine thread runs step().
    # Plain '_lock' = every access under the lock (or in a method
    # annotated '# holds: _lock' whose callers all hold it);
    # '_lock:mut' = single-writer discipline — the engine thread owns
    # the field and MUTATES it only under the lock so cross-thread
    # readers (metrics/idle, which do lock) never see a torn update,
    # while the owner's own reads stay lock-free.
    _GUARDED_BY = {
        '_sched': '_lock',          # submit() threads vs step loop —
                                    # the scheduler's own fields are
                                    # declared in infer/sched/ and
                                    # guarded by THIS lock too
        '_ttfts': '_lock',          # consume appends vs snapshots
        '_queue_waits': '_lock',
        '_slots': '_lock:mut',      # engine-thread owned
        '_inflight_tok': '_lock:mut',
        # Throughput accumulators: submit()'s Retry-After estimate and
        # metrics()' tokens_per_step read the (tokens, steps, time)
        # TRIPLE under the lock — the engine thread must mutate each
        # member under it too, or a reader between two of the
        # increments computes a rate from a half-applied pair (the
        # PR 6 _inflight_tok bug class; found by SKY-LOCK v2 at
        # bring-up: _decode_time/_decode_steps were bumped outside).
        '_decode_tokens': '_lock:mut',
        '_decode_steps': '_lock:mut',
        '_decode_time': '_lock:mut',
        # Prefill-stall decomposition gauges (metrics() reads the
        # set under the lock; the engine thread bumps them there too).
        '_prefill_tokens': '_lock:mut',
        '_fused_steps': '_lock:mut',
        '_stall_steps': '_lock:mut',
        '_abandoned': '_lock',      # sweep writes vs metrics reads
        '_expired': '_lock',
        '_cancelled': '_lock',
        '_preemptions': '_lock',
        '_spec_k': '_lock',         # set_spec_k threads vs step loop
        '_spec_pinned': '_lock',
        '_spec_steps': '_lock',     # consume writes vs metrics reads
        '_spec_slot_steps': '_lock',
        '_spec_drafted': '_lock',
        '_spec_accepted': '_lock',
        '_spec_emitted': '_lock',
        # Flight recorder: the step loop appends records under the
        # lock; HTTP snapshot readers (stepline_snapshot) copy under
        # it too — the rings themselves own no lock (the scheduler
        # contract). _pending_dumps defers anomaly-dump handoff to
        # OUTSIDE the lock so the engine lock never nests the dump
        # writer's condition (LOCK_ORDER stays leaf-level).
        '_stepline': '_lock',
        '_pending_dumps': '_lock',
        # SDC sentinel: consume bumps under the lock; metrics reads
        # under it. (_integrity_suspect itself is a GIL-atomic one-way
        # bool like the server's ready/dead flags — readers tolerate
        # one stale step.)
        '_sdc_events': '_lock',
        # Fleet KV transfers: HTTP threads enqueue jobs and read the
        # published index/counters; the stepping thread pops jobs and
        # publishes — all handoffs under the lock (the tree and pool
        # themselves stay engine-thread-confined).
        '_kv_jobs': '_lock',
        '_kv_transfers': '_lock',
        '_kv_transfer_bytes': '_lock',
        '_kv_transfer_failures': '_lock',
        '_kv_transfer_window': '_lock',
        '_kv_index_pub': '_lock',
        '_model_counters': '_lock',  # consume adds vs metrics reads
        # Step-program launches and how many found the device's queue
        # empty: metrics() reads the three together.
        '_launches': '_lock:mut',
        '_launches_dev_empty': '_lock:mut',
        '_launches_after_wait': '_lock:mut',
        # First tokens stamped, and those read early (by their chunk's
        # own record, not a step pair's row 0).
        '_first_tokens': '_lock:mut',
        '_first_tokens_early': '_lock:mut',
    }

    def __init__(self, config: llama.LlamaConfig, params: llama.Params,
                 engine_config: Optional[EngineConfig] = None,
                 seed: int = 0) -> None:
        self.config = config
        self.ecfg = engine_config or EngineConfig()
        # The serving half of the model interface (models/interface.py):
        # what state the model's layers keep, its step programs, and
        # the switches it refuses (raised here, with the reason).
        interface.check_engine(config, self.ecfg)
        spec = interface.cache_spec(config)
        steps = model_lib.paged_steps(config)
        self._state_spec = spec.state
        # Counts the decode program returns beside its logits (a hybrid
        # model's expert and state counters), carried out on the step's
        # pair and summed here; /metrics shows them.
        self._step_stats: Tuple[str, ...] = (
            steps.stats if self.ecfg.paged else ())
        self._model_counters: Dict[str, int] = {
            name: 0 for name in self._step_stats}
        if self.ecfg.max_seq_len > config.max_seq_len:
            raise ValueError(
                f'cache max_seq_len {self.ecfg.max_seq_len} exceeds model '
                f'max_seq_len {config.max_seq_len}')
        # Chunk buckets: prefill_buckets clamped to the chunk cap (and
        # the cache length). Non-final chunks always use the cap, so
        # write offsets stay multiples of it; requiring cap | max_seq_len
        # keeps every padded chunk write inside the cache
        # (dynamic_update_slice clamps out-of-range starts, which would
        # silently corrupt earlier positions).
        cap = min(self.ecfg.prefill_chunk, self.ecfg.max_seq_len)
        self._buckets = sorted(
            {min(b, cap) for b in self.ecfg.prefill_buckets} | {cap})
        self._chunk_cap = self._buckets[-1]
        if self.ecfg.max_seq_len % self._chunk_cap:
            raise ValueError(
                f'max_seq_len {self.ecfg.max_seq_len} must be a '
                f'multiple of the chunk size {self._chunk_cap}')
        if self.ecfg.quantize:
            from skypilot_tpu.ops import quant as quant_lib
            if not quant_lib.is_quantized(params):
                params = quant_lib.quantize_params(params)
        self.params = params
        self.allocator: Optional[paged_cache_lib.PageAllocator] = None
        self.window_alloc: Optional[paged_cache_lib.WindowAllocator] = None
        if self.ecfg.paged:
            if self.ecfg.tp > 1:
                raise ValueError(
                    'paged KV is single-device for now (the Pallas '
                    'kernels are not yet shard_map-wrapped); use the '
                    'dense cache for tp > 1')
            page = self.ecfg.page_size
            if self._chunk_cap % page:
                raise ValueError(
                    f'prefill chunk {self._chunk_cap} must be a '
                    f'multiple of page_size {page}')
            # Buckets must cover whole pages (chunk writes are
            # whole-page dynamic_update_slices), and the ladder must be
            # page-granular enough that a short tail never allocates a
            # cap-sized pad (power-of-two multiples of the page bound
            # the overshoot at 2x while keeping compile count small).
            ladder = set()
            b = page
            while b < self._chunk_cap:
                ladder.add(b)
                b *= 2
            self._buckets = sorted(
                {b for b in self._buckets if b % page == 0}
                | ladder | {self._chunk_cap})
            if not self.ecfg.prefill_tail_buckets:
                self._buckets = [self._chunk_cap]
            max_pages_per_slot = self.ecfg.max_seq_len // page
            n_pages = self.ecfg.n_pages
            if n_pages is None:
                n_pages = self.ecfg.n_slots * max_pages_per_slot + 1
            min_pages = self._chunk_cap // page + 1
            if n_pages < min_pages:
                raise ValueError(
                    f'n_pages={n_pages} cannot hold one prefill chunk '
                    f'(needs >= {min_pages} incl. the sink page)')
            self.allocator = paged_cache_lib.PageAllocator(
                n_pages, page, self.ecfg.n_slots, max_pages_per_slot)
            if self.ecfg.kv_dtype not in ('bfloat16', 'int8'):
                raise ValueError(
                    f"kv_dtype must be 'bfloat16' or 'int8', got "
                    f'{self.ecfg.kv_dtype!r}')
            kv_dtype = (jnp.int8 if self.ecfg.kv_dtype == 'int8'
                        else jnp.dtype(self.ecfg.cache_dtype))
            extra = {}
            if spec.latent is not None and spec.latent.window_layers:
                # Window layers keep their rows in a pool of their own,
                # bounded by the window and one chunk a slot whatever
                # the contexts are (paged_cache.WindowAllocator).
                self.window_alloc = paged_cache_lib.WindowAllocator(
                    page, self.ecfg.n_slots, max_pages_per_slot,
                    spec.latent.window, self._chunk_cap)
                extra['window_pages'] = self.window_alloc.n_pages
            self.cache = steps.init_cache(
                spec, self.ecfg.n_slots, n_pages, page, kv_dtype, **extra)
        else:
            if self.ecfg.kv_dtype not in ('bfloat16',):
                raise ValueError(
                    'kv_dtype=int8 requires the paged KV cache '
                    '(EngineConfig.paged=True): quantization is at '
                    'page granularity')
            if self.ecfg.prefix_cache:
                raise ValueError(
                    'prefix_cache requires the paged KV cache '
                    '(EngineConfig.paged=True): sharing is at page '
                    'granularity')
            self.cache = cache_lib.init_cache(
                config.n_layers, self.ecfg.n_slots,
                self.ecfg.max_seq_len, config.n_kv_heads,
                config.head_dim, dtype=jnp.dtype(self.ecfg.cache_dtype))
        self.mesh = None
        self._rep_sharding = None
        self._cache_sharding = None
        if self.ecfg.tp > 1:
            self._shard_tp()
        self._key = jax.random.PRNGKey(seed)
        self._ids = itertools.count(1)
        # Reentrant: _finish/_preempt take it for their slot/page
        # mutations and are also called from _consume_one, which
        # already holds it for the whole consume.
        self._lock = threading.RLock()
        # Pluggable admission/ordering policy (infer/sched/): owns the
        # waiting queue; every call into it happens under _lock.
        self._sched = sched_lib.make(
            self.ecfg.scheduler,
            sched_lib.SchedulerConfig(
                max_queue_requests=self.ecfg.max_queue_requests,
                max_queue_tokens=self.ecfg.max_queue_tokens,
                tenant_weights=self.ecfg.tenant_weights))
        self._slots: List[Optional[Request]] = [None] * self.ecfg.n_slots
        # Shared-prefix radix tree over the page pool (None = disabled).
        self.prefix: Optional[prefix_cache_lib.PrefixCache] = None
        # Slots that already ran their prefix match for the current
        # residency (a rolled-back attach discards the entry so the
        # retry re-matches).
        self._matched: set = set()
        # Slots currently mapping attached (possibly shared) pages —
        # the only slots _unshare_write_range must scan; everyone else
        # skips the per-token refcount walk entirely.
        self._attached_slots: set = set()
        if self.ecfg.prefix_cache:
            self.prefix = prefix_cache_lib.PrefixCache(self.allocator)
        # ---- fleet KV transfer state (docs/serving.md "Disaggregated
        # prefill/decode"): queued export/import jobs serviced at step
        # start, transfer counters + a bounded duration window for the
        # p99, and the last published index snapshot (gen, crc, page,
        # journal, hashes) the HTTP thread builds wire summaries from.
        self._kv_jobs: collections.deque = collections.deque()
        self._kv_transfers = 0
        self._kv_transfer_bytes = 0
        self._kv_transfer_failures = 0
        self._kv_transfer_window: collections.deque = collections.deque(
            maxlen=512)
        self._kv_index_pub: tuple = (
            0, 0,
            self.allocator.page_size if self.allocator is not None
            else 0, (), frozenset())
        # slot -> prompt tokens already prefilled (chunked prefill in
        # flight); a slot decodes only once its prompt is fully cached.
        self._prefilling: Dict[int, int] = {}
        # Last sampled token per slot lives ON DEVICE: reading it back
        # per step would add a host sync (decode consumes it directly;
        # the host sees tokens through the decode output pair).
        self._last_dev = jnp.zeros((self.ecfg.n_slots,), jnp.int32)
        if self._rep_sharding is not None:
            self._last_dev = jax.device_put(self._last_dev,
                                            self._rep_sharding)
        self._slot_len = np.zeros((self.ecfg.n_slots,), np.int64)
        self._temps = np.zeros((self.ecfg.n_slots,), np.float32)
        # ---- overlapped decode pipeline state ---------------------------
        # Dispatched-but-unread results (infer/inflight.py): step
        # pairs (≤ _depth of them) and, ahead of the decode dispatched
        # behind a prompt's last chunk, that chunk's first token. Each
        # record pins the device result (async host copy in flight)
        # plus the slot→request assignment AT DISPATCH TIME, so consume
        # can apply the stale-by-one rule: a token whose slot no longer
        # holds the same request (finished / preempted meanwhile) is
        # dropped.
        self._depth = max(0, int(self.ecfg.pipeline_depth))
        self._queue = inflight.Queue()
        # Per-slot count of tokens in flight (page accounting must cover
        # positions the device will have written before the host reads).
        self._inflight_tok = [0] * self.ecfg.n_slots
        # Device-resident copies of per-token decode operands, re-uploaded
        # only when dirtied by submit/finish/preempt/extend — not three
        # jnp.asarray uploads per token.
        self._temps_dev = None
        self._temps_dirty = True
        self._active_dev = None
        self._active_key: Optional[tuple] = None
        self._table_dev = None
        self._table_version = -1
        self._decode_steps = 0
        self._decode_tokens = 0
        self._decode_time = 0.0
        self._preemptions = 0
        # ---- fused mixed-step state -------------------------------------
        self._fused = bool(self.ecfg.fused_prefill)
        # Prefill-stall decomposition: prompt tokens dispatched into
        # prefill chunks (fused or standalone), fused mixed dispatches,
        # and steps where an active decode batch waited on a
        # STANDALONE prefill dispatch (the ITL stall fused mode
        # removes — ~0 with fused_prefill on).
        self._prefill_tokens = 0
        self._fused_steps = 0
        self._stall_steps = 0
        # Zero-downtime-serving counters: queued requests dropped
        # because the client vanished, requests cut by their deadline,
        # active requests cancelled by a client disconnect.
        self._abandoned = 0
        self._expired = 0
        self._cancelled = 0
        # ---- speculative decoding state ---------------------------------
        # Runtime draft-width knob (set_spec_k); 0 = off. The lockstep
        # driver PINS it off (pin_spec_off) — re-enabling then raises.
        self._spec_k = max(0, int(self.ecfg.spec_k))
        self._spec_pinned = False
        self._drafter = drafter_lib.PromptLookupDrafter(
            max_ngram=max(1, int(self.ecfg.spec_ngram)))
        # Verify accounting: dispatches, (slot, step) lanes, drafted /
        # accepted draft tokens, tokens emitted via verify consumes.
        self._spec_steps = 0
        self._spec_slot_steps = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_emitted = 0
        # ---- SDC sentinel state -----------------------------------------
        # _sentinel gates the trace-time branch that appends the
        # finite-flags row to decode/mixed/verify outputs; immutable
        # after init (compiled programs bake it in). _integrity_suspect
        # is a one-way GIL-atomic flag (the server's ready/dead rule):
        # flipped by the engine thread on the first NaN/inf hit, read
        # lock-free by /health and /generate admission.
        self._sentinel = bool(self.ecfg.sdc_sentinel)
        self._integrity_suspect = False
        self._sdc_events = 0
        # Wall-clock sweeps (deadline / cancel) read the LOCAL clock;
        # the multihost lockstep driver disables them — every host must
        # make identical request-state decisions each tick.
        self.wallclock_cancel = True
        # Recent-window TTFTs: bounded so a long-lived replica's /metrics
        # stays O(1) in memory and p50 reflects current behavior.
        self._ttfts: collections.deque = collections.deque(maxlen=1024)
        # Recent-window queue waits (submit → first chunk dispatch):
        # the scheduling share of TTFT, reported separately so a
        # scheduling win is attributable apart from prefill speed.
        self._queue_waits: collections.deque = collections.deque(
            maxlen=1024)
        # ---- flight recorder (observability/stepline.py) ----------------
        # Always on: every per-layer metric of the benchmark and
        # /debug/stepline read it. The rings are lock-guarded state.
        self._stepline = stepline_lib.StepRecorder(
            self.ecfg.stepline_cap)
        self._pending_dumps: List[tuple] = []
        # Engine-thread stage timer, reset at each step start (never
        # read cross-thread): `with self._stage('dispatch'):` adds the
        # block's wall time to the step's record and annotates it in a
        # profiler trace. dispatch = device program launches, drain =
        # consume bookkeeping, readback = blocked on the pair's
        # device→host copy, sched = the step's admission section.
        # Stage `wait` lies between steps: the server loop's block for
        # work (`wait_stage`).
        self._sl_clock = stepline_lib.StageClock()
        self._stage = self._sl_clock.stage
        self._sl_batch = 0
        self._sl_dev_empty = 0
        self._launches = 0
        self._launches_dev_empty = 0
        self._launches_after_wait = 0
        self._first_tokens = 0
        self._first_tokens_early = 0

        # ---- compiled programs ------------------------------------------
        # Params are ARGUMENTS, never closure-captured: captured arrays
        # are baked into the lowered program as constants — for a 1B+
        # model that is gigabytes of constants, a pathological compile,
        # and a second copy of the weights in the executable.
        def _jit(fn, *, donate=(), out=None):
            kw = {}
            if donate:
                kw['donate_argnums'] = donate
            if out is not None and self.mesh is not None:
                kw['out_shardings'] = out
            return jax.jit(fn, **kw)

        def _finite_row(logits):
            # SDC sentinel row: per-slot "every logit finite" flags,
            # reduced over every non-slot axis (vocab, plus the
            # candidate axis in verify) ON DEVICE — int32 so the row
            # stacks with the token rows and rides the existing
            # readback, costing zero extra transfers. Appended LAST so
            # every existing consume index is unchanged.
            axes = tuple(range(1, logits.ndim))
            return jnp.all(jnp.isfinite(logits),
                           axis=axes).astype(jnp.int32)

        def _first_out(tok, logits):
            # What a prompt's last chunk hands the early read
            # (inflight.FirstToken): the token it sampled and, sentinel
            # on, whether the ONE row of logits it sampled from is all
            # finite. A [vocab] reduce a chunk, not a step.
            rows = [tok.astype(jnp.int32)]
            if self._sentinel:
                rows.append(jnp.all(jnp.isfinite(logits)).astype(
                    jnp.int32))
            return jnp.stack(rows)

        def _accept(tokens, logits, drafts, draft_len, key, temps,
                    active, lengths):
            # Shared tail of both verify programs: exact-greedy draft
            # acceptance plus the device-side state advance, FUSED with
            # the verify forward pass so the device never waits on a
            # host decision — lengths advance by accepted+1 and the
            # corrected token becomes the next step's input ON DEVICE;
            # the host reads the [spec_k+3, slots] pair back async
            # (row 0 input echo, rows 1..spec_k+1 emitted candidates,
            # last row the accepted count) purely for bookkeeping.
            emitted, accepted = sampling_lib.speculative_accept(
                logits, drafts, draft_len, key, temps,
                top_k=self.ecfg.top_k)
            accepted = jnp.where(active, accepted, 0)
            next_tok = jnp.take_along_axis(
                emitted, accepted[:, None], axis=1)[:, 0]
            new_last = jnp.where(active, next_tok,
                                 tokens[:, 0]).astype(tokens.dtype)
            bump = jnp.where(active, accepted + 1, 0).astype(
                lengths.dtype)
            pair = jnp.concatenate(
                [tokens[:, :1].T.astype(jnp.int32), emitted.T,
                 accepted[None].astype(jnp.int32)], axis=0)
            if self._sentinel:
                pair = jnp.concatenate(
                    [pair, _finite_row(logits)[None]], axis=0)
            return pair, new_last, lengths + bump

        if self.ecfg.paged:
            def _prefill_chunk_paged(kv_cache, params, slot, table_row,
                                     tokens, offset, true_len, key,
                                     temp, last):
                new_cache, logits = steps.prefill_chunk(
                    config, params, kv_cache, slot, table_row, tokens,
                    offset, true_len)
                tok = sampling_lib.sample(logits[None], key, temp[None],
                                          top_k=self.ecfg.top_k)[0]
                return (new_cache, last.at[slot].set(
                    tok.astype(last.dtype)), _first_out(tok, logits))
            self._prefill_chunk = _jit(_prefill_chunk_paged,
                                       donate=(0, 9))

            def _decode_paged(kv_cache, params, tables, tokens, key,
                              temps, active):
                logits, new_cache, *stats = steps.decode(
                    config, params, kv_cache, tables, tokens, active)
                sampled = sampling_lib.sample(logits, key, temps,
                                              top_k=self.ecfg.top_k)
                toks_out = jnp.where(active, sampled, tokens)
                rows = [tokens, toks_out]
                # A family's step counts ride the pair as one row each
                # (the value in every column), after the token rows and
                # before the sentinel's, which consume reads as the last.
                # (``stats`` holds one vector of them, or nothing.)
                rows += [jnp.full_like(tokens, c)
                         for counts in stats for c in counts]
                if self._sentinel:
                    rows.append(_finite_row(logits))
                return jnp.stack(rows), new_cache
            self._decode = _jit(_decode_paged, donate=(0,))

            def _free_paged(kv_cache, slot):
                return steps.free_slot(kv_cache, slot)
            self._free = _jit(_free_paged, donate=(0,))

            def _verify_paged(kv_cache, params, tables, last, drafts,
                              draft_len, key, temps, active):
                tokens = jnp.concatenate([last[:, None], drafts],
                                         axis=1)
                logits, new_cache = steps.verify(
                    config, params, kv_cache, tables, tokens)
                pair, new_last, lengths = _accept(
                    tokens, logits, drafts, draft_len, key, temps,
                    active, new_cache.lengths)
                return pair, new_last, dataclasses.replace(
                    new_cache, lengths=lengths)
            self._verify = _jit(_verify_paged, donate=(0,))

            def _mixed_paged(kv_cache, params, slot, table_row,
                             chunk_tokens, offset, true_len, chunk_key,
                             chunk_temp, tables, last, key, temps,
                             active):
                # One fused launch: the chunk's first-token sample
                # lands in the last-token vector (meaningful only on
                # the final chunk, like the standalone prefill), the
                # decode half samples every active slot — pair row 0
                # echoes the post-chunk last vector so a completing
                # chunk's first token surfaces through the SAME host
                # read as the decode tokens.
                chunk_logits, dec_logits, new_cache = (
                    steps.mixed(
                        config, params, kv_cache, slot, table_row,
                        chunk_tokens, offset, true_len, tables, last,
                        active))
                first = sampling_lib.sample(
                    chunk_logits[None], chunk_key, chunk_temp[None],
                    top_k=self.ecfg.top_k)[0]
                last1 = last.at[slot].set(first.astype(last.dtype))
                sampled = sampling_lib.sample(dec_logits, key, temps,
                                              top_k=self.ecfg.top_k)
                toks_out = jnp.where(active, sampled, last1)
                rows = [last1, toks_out]
                if self._sentinel:
                    # The chunk slot's flag folds in the chunk logits
                    # too — a NaN in the fused prefill half must not
                    # hide behind a clean decode half.
                    flags = _finite_row(dec_logits)
                    chunk_ok = jnp.all(jnp.isfinite(
                        chunk_logits)).astype(jnp.int32)
                    flags = flags.at[slot].set(flags[slot] * chunk_ok)
                    rows.append(flags)
                return jnp.stack(rows), new_cache
            self._mixed = _jit(_mixed_paged, donate=(0,))

            if self.ecfg.prefix_cache:
                # Copy-on-write page duplication. src/dst are traced
                # scalars: ONE compiled program serves every CoW, so
                # enabling the prefix cache adds zero compilations to
                # the steady-state workload (this program only compiles
                # if a CoW ever fires).
                def _cow_paged(kv_cache, src, dst):
                    return paged_cache_lib.copy_page(kv_cache, src, dst)
                self._cow = _jit(_cow_paged, donate=(0,))
        else:
            def _prefill_chunk(kv_cache, params, slot, tokens, offset,
                               true_len, key, temp, last):
                # One compiled program per chunk bucket (tokens shape).
                # First-token sampling AND the last-token vector update
                # are FUSED: separate programs would cost extra
                # dispatches (and a sample sync) per prompt. The
                # sampled token is only meaningful on the final chunk
                # (whose third result the host reads early); earlier
                # chunks' updates are overwritten before the slot ever
                # decodes.
                new_cache, logits = model_lib.prefill_chunk(
                    config, params, kv_cache, slot, tokens, offset,
                    true_len)
                tok = sampling_lib.sample(logits[None], key, temp[None],
                                          top_k=self.ecfg.top_k)[0]
                return (new_cache, last.at[slot].set(
                    tok.astype(last.dtype)), _first_out(tok, logits))
            self._prefill_chunk = _jit(
                _prefill_chunk, donate=(0, 8),
                out=(self._cache_sharding, self._rep_sharding,
                     self._rep_sharding))

            def _decode(kv_cache, params, tokens, key, temps, active):
                logits, new_cache = model_lib.decode_step(
                    config, params, kv_cache, tokens, active)
                sampled = sampling_lib.sample(logits, key, temps,
                                              top_k=self.ecfg.top_k)
                toks_out = jnp.where(active, sampled, tokens)
                # [2, slots]: row 0 echoes the inputs, row 1 the new
                # tokens — ONE host read serves both.
                rows = [tokens, toks_out]
                if self._sentinel:
                    rows.append(_finite_row(logits))
                return jnp.stack(rows), new_cache
            self._decode = _jit(
                _decode, donate=(0,),
                out=(self._rep_sharding, self._cache_sharding))

            def _free(kv_cache, slot):
                return cache_lib.free_slot(kv_cache, slot)
            self._free = _jit(_free, donate=(0,),
                              out=self._cache_sharding)

            def _verify_dense(kv_cache, params, last, drafts,
                              draft_len, key, temps, active):
                tokens = jnp.concatenate([last[:, None], drafts],
                                         axis=1)
                logits, new_cache = model_lib.verify_step(
                    config, params, kv_cache, tokens)
                pair, new_last, lengths = _accept(
                    tokens, logits, drafts, draft_len, key, temps,
                    active, new_cache.lengths)
                return pair, new_last, cache_lib.KVCache(
                    k=new_cache.k, v=new_cache.v, lengths=lengths)
            self._verify = _jit(
                _verify_dense, donate=(0,),
                out=(self._rep_sharding, self._rep_sharding,
                     self._cache_sharding))

            def _mixed_dense(kv_cache, params, slot, chunk_tokens,
                             offset, true_len, chunk_key, chunk_temp,
                             last, key, temps, active):
                chunk_logits, dec_logits, new_cache = (
                    model_lib.mixed_step(
                        config, params, kv_cache, slot, chunk_tokens,
                        offset, true_len, last, active))
                first = sampling_lib.sample(
                    chunk_logits[None], chunk_key, chunk_temp[None],
                    top_k=self.ecfg.top_k)[0]
                last1 = last.at[slot].set(first.astype(last.dtype))
                sampled = sampling_lib.sample(dec_logits, key, temps,
                                              top_k=self.ecfg.top_k)
                toks_out = jnp.where(active, sampled, last1)
                rows = [last1, toks_out]
                if self._sentinel:
                    flags = _finite_row(dec_logits)
                    chunk_ok = jnp.all(jnp.isfinite(
                        chunk_logits)).astype(jnp.int32)
                    flags = flags.at[slot].set(flags[slot] * chunk_ok)
                    rows.append(flags)
                return jnp.stack(rows), new_cache
            self._mixed = _jit(
                _mixed_dense, donate=(0,),
                out=(self._rep_sharding, self._cache_sharding))

    def _shard_tp(self) -> None:
        """Distribute params + KV cache over a `tp` mesh axis.

        Reuses the training sharding rules (parallel/sharding.py:
        attention/MLP column+row parallel, vocab-parallel embed/lm_head)
        on a (tp, fsdp=1) mesh; the KV cache shards over KV heads. The
        compiled prefill/decode programs are untouched — GSPMD partitions
        them from the input shardings and inserts the collectives.
        """
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from skypilot_tpu.parallel import sharding as sharding_lib
        tp = self.ecfg.tp
        cfg = self.config
        for dim_name, dim in (('n_heads', cfg.n_heads),
                              ('n_kv_heads', cfg.n_kv_heads),
                              ('ffn_dim', cfg.ffn_dim),
                              ('vocab_size', cfg.vocab_size)):
            if dim % tp:
                raise ValueError(
                    f'tp={tp} must divide {dim_name}={dim}')
        mesh = tp_mesh(tp)
        self.mesh = mesh
        self.params = sharding_lib.shard_pytree(
            self.params, sharding_lib.param_shardings(mesh, self.params))
        kv_spec = NamedSharding(mesh, P(None, None, None, 'tp', None))
        rep = NamedSharding(mesh, P())
        self.cache = cache_lib.KVCache(
            k=jax.device_put(self.cache.k, kv_spec),
            v=jax.device_put(self.cache.v, kv_spec),
            lengths=jax.device_put(self.cache.lengths, rep))
        # Host-consumed outputs (sampled tokens, logits) must be FULLY
        # REPLICATED: when the tp axis spans processes (multi-host
        # replica), np.asarray on a non-replicated global array raises
        # 'spans non-addressable devices'. The cache keeps its sharding.
        self._rep_sharding = rep
        self._cache_sharding = cache_lib.KVCache(k=kv_spec, v=kv_spec,
                                                 lengths=rep)

    # ---- submission ------------------------------------------------------
    def submit(self, prompt_tokens: Sequence[int],
               max_new_tokens: Optional[int] = None,
               temperature: float = 0.0,
               resume_tokens: Optional[Sequence[int]] = None,
               deadline: Optional[float] = None,
               tenant: str = sched_lib.DEFAULT_TENANT,
               spec: bool = True,
               recv_t: Optional[float] = None,
               lb_recv_t: Optional[float] = None) -> Request:
        """Queue a request. ``resume_tokens`` continues a stream whose
        earlier tokens were already delivered elsewhere (mid-stream
        failover): they are pre-seeded into ``output_tokens``, so
        prefill covers prompt+resume (the same recompute path as paged
        preemption — greedy continuation is bit-identical to an
        uninterrupted run) and decoding picks up at the boundary.
        ``deadline`` is an absolute wall-clock cutoff enforced by the
        step loop. ``tenant`` is the fair-queueing identity
        (X-SkyTpu-Tenant). ``spec=False`` opts this request out of
        speculative drafting (outputs are identical; only step count
        changes). ``recv_t`` / ``lb_recv_t`` are the wall-clock
        moments the server's handler and the serve LB received the
        request: observed only, they ride the flight recorder's
        ``submit`` event. Raises
        :class:`AdmissionError` when the scheduler's (global or
        per-tenant) queue bound is hit."""
        if not prompt_tokens:
            raise ValueError('empty prompt')
        resume = list(map(int, resume_tokens)) if resume_tokens else []
        total = len(prompt_tokens) + len(resume)
        if total > self.ecfg.max_seq_len - 1:
            raise ValueError(
                f'prompt+resume ({total} tokens) exceeds cache '
                f'capacity ({self.ecfg.max_seq_len - 1})')
        if self.allocator is not None:
            # Peak prefill allocation is BUCKET-padded (the final chunk
            # writes its whole padded bucket), plus one decode page —
            # admitting on the raw token count would accept requests
            # that can never finish prefill (starvation, not an error).
            n = total
            off = (n // self._chunk_cap) * self._chunk_cap
            rem = n - off
            peak = self.allocator.pages_needed(
                off + (self._bucket(rem) if rem else 0)) + 1
            if peak > self.allocator.n_pages - 1:
                raise ValueError(
                    f'prompt+resume ({n} tokens; {peak} pages incl. '
                    f'padding + first decode page) exceeds the page '
                    f'pool ({self.allocator.n_pages - 1} usable pages '
                    f'x {self.allocator.page_size})')
        if max_new_tokens is None:
            max_new_tokens = self.ecfg.max_new_tokens
        if max_new_tokens < 1:
            raise ValueError('max_new_tokens must be >= 1')
        req = Request(
            request_id=next(self._ids),
            prompt_tokens=list(map(int, prompt_tokens)),
            max_new_tokens=max_new_tokens,
            temperature=float(temperature),
            output_tokens=resume,
            resumed_from=len(resume),
            deadline=deadline,
            tenant=str(tenant) or sched_lib.DEFAULT_TENANT,
            spec=bool(spec))
        if resume and len(resume) >= max_new_tokens:
            # The stream died on its very last token: the budget is
            # already spent — finish without ever entering the queue
            # (the caller emits the done line immediately).
            req.finish_reason = 'max_tokens'
            req.finished_at = time.time()
            return req
        try:
            # Chaos seam: force the shed path without actually filling
            # the queue.
            failpoints.hit('infer.engine.admit_full')
        except failpoints.FailpointError as e:
            raise AdmissionError(f'injected admit-full: {e}') from e
        try:
            with self._lock:
                # Admission is the scheduler's call (global bounds under
                # fcfs/deadline, per-tenant quotas under wfq); its
                # AdmissionError carries a queue-drain Retry-After
                # estimate computed from the recent decode throughput.
                # _decode_tokens counts EMITTED tokens — under speculation
                # a verify step lands 1..spec_k+1 of them — so the
                # estimate's tokens/sec is the accepted-length-aware
                # EFFECTIVE rate, not a 1-token/step assumption that would
                # overshoot 429 backoff hints by the acceptance factor.
                try:
                    self._sched.admit(req, drain_tps=(
                        self._decode_tokens / self._decode_time
                        if self._decode_time else 0.0))
                except AdmissionError:
                    # Anomaly trigger: an admission shed is exactly the
                    # incident the black box exists for — what was the
                    # engine doing when it started refusing work?
                    self._note_anomaly('admission_shed', {
                        'request_id': req.request_id,
                        'tenant': req.tenant,
                        'prompt_tokens': len(req.prompt_tokens)})
                    raise
                self._sched.enqueue(req)
                self._stepline.note_event(
                    req.request_id, req.tenant, 'submit',
                    req.submitted_at,
                    prompt_tokens=len(req.prompt_tokens),
                    **({'resumed_from': req.resumed_from}
                       if req.resumed_from else {}),
                    **({'recv_t': recv_t}
                       if recv_t is not None else {}),
                    **({'lb_recv_t': lb_recv_t}
                       if lb_recv_t is not None else {}))
        finally:
            # Outside the lock: the dump handoff takes the writer's
            # own condition, which must never nest under the engine
            # lock. A shed request still flushes its dump.
            self._flush_stepline_dumps()
        return req

    def cancel(self, req: Request) -> bool:
        """Request cancellation (thread-safe, cooperative): flags the
        request; the engine thread drops it at its next step — a queued
        request never admits ('requests_abandoned' — it stops occupying
        an admission-control queue slot immediately), an active one
        finishes 'cancelled' with its pages donated to the prefix cache
        or freed. Returns False when the request already finished."""
        with self._lock:
            if req.done:
                return False
            req.cancelled = True
        return True

    # ---- fleet KV transfers (docs/serving.md "Disaggregated
    # prefill/decode") --------------------------------------------------
    def kv_index_armed(self) -> bool:
        """Whether this engine advertises a fleet prefix index."""
        return self.prefix is not None

    def kv_page_size(self) -> int:
        """KV page size in tokens (0 when unpaged) — the server's
        export-cap arithmetic needs it without reaching into cfg."""
        return self.ecfg.page_size if self.ecfg.paged else 0

    def kv_index_snapshot(self, since_gen: int = -1
                          ) -> Optional[Dict[str, Any]]:
        """Wire summary of the radix index for the LB's sync tick,
        delta-encoded against ``since_gen``. Thread-safe: built from
        the step loop's published copy, never the live tree. None when
        the prefix cache is off (the index is unarmed)."""
        if self.prefix is None:
            return None
        with self._lock:
            gen, crc, page, journal, hashes = self._kv_index_pub
        return prefix_hash.build_snapshot(gen, crc, page, journal,
                                          hashes, since_gen)

    def _refuse_kv_wire(self) -> None:
        reason = interface.refusals(self.config).get('kv_wire')
        if reason:
            raise ValueError(
                f'{type(self.config).__name__} cannot export or import '
                f'KV pages (kv_wire): {reason}')

    def request_kv_export(self, tokens: Sequence[int]) -> _KVJob:
        """Queue an export of the cached prefix of ``tokens`` (any
        thread). The stepping thread serializes it at its next step;
        ``job.result`` is the wire blob, or None when nothing is
        cached. The donor's refcounts are never touched."""
        self._refuse_kv_wire()
        job = _KVJob('export', list(tokens))
        with self._lock:
            self._kv_jobs.append(job)
        return job

    def request_kv_import(self, blob: bytes,
                          fetch_s: float = 0.0) -> _KVJob:
        """Queue the import of a transferred prefix blob (any thread).
        ``fetch_s`` — the upstream pull's wall time — folds into the
        transfer-duration window so ``kv_transfer_p99_s`` prices the
        whole pull, not just the local attach."""
        self._refuse_kv_wire()
        job = _KVJob('import', blob, fetch_s=fetch_s)
        with self._lock:
            self._kv_jobs.append(job)
        return job

    def note_kv_transfer_failure(self) -> None:
        """Count a transfer that died before reaching the engine
        (donor fetch error, stall timeout) — the replica's failure
        counter covers the whole pull path, not just the attach."""
        with self._lock:
            self._kv_transfer_failures += 1

    def kv_transfer_window(self) -> List[float]:
        """Recent per-transfer durations (bounded window), snapshotted
        under the lock — same contract as ttft_window."""
        with self._lock:
            return list(self._kv_transfer_window)

    def _service_kv_jobs(self) -> None:
        """Pop and run queued KV transfer jobs, then (re)publish the
        index snapshot — on the STEPPING thread, which owns the tree
        and the page pool. The device readback (export) and scatter
        (import) run OUTSIDE the lock: a transfer must never block
        submit() on a device sync."""
        with self._lock:
            jobs = list(self._kv_jobs)
            self._kv_jobs.clear()
        for job in jobs:
            t0 = time.perf_counter()
            try:
                if job.kind == 'export':
                    result = self._kv_export(job.payload)
                else:
                    result = self._kv_import(job.payload)
            except Exception as exc:
                # Degrade, never crash the step loop: the caller
                # recomputes (the fallback contract) and the failure
                # is counted.
                with self._lock:
                    self._kv_transfer_failures += 1
                job.finish(error=exc)
                continue
            if job.kind == 'export' and result is None:
                job.finish(result=None)   # nothing cached: not a
                continue                  # transfer, not a failure
            dur = time.perf_counter() - t0 + job.fetch_s
            nbytes = (len(result) if job.kind == 'export'
                      else len(job.payload))
            with self._lock:
                self._kv_transfers += 1
                self._kv_transfer_bytes += nbytes
                self._kv_transfer_window.append(dur)
            job.finish(result=result)
        if self.prefix is not None:
            pub = self.prefix.publishable()
            with self._lock:
                if pub[0] != self._kv_index_pub[0]:
                    self._kv_index_pub = pub

    def _kv_export(self, tokens: List[int]) -> Optional[bytes]:
        """Serialize the cached prefix of ``tokens`` into the int8
        wire format (engine thread). bf16 pools quantize on export
        with the exact scheme the int8 cache uses on write. Returns
        None when no prefix is cached. READ-ONLY: no refcount moves,
        no LRU touch — and no eviction point between the peek and the
        readback (both on the owner thread within one servicing)."""
        if self.prefix is None or self.allocator is None:
            raise ValueError(
                'KV export requires the paged prefix cache')
        pages, matched = self.prefix.peek(tokens)
        if not pages:
            return None
        k, v, ks, vs = paged_cache_lib.gather_pages(self.cache, pages)
        if ks is not None:
            kq, vq, ks, vs = (np.asarray(a) for a in (k, v, ks, vs))
        else:
            kq, ks = kv_wire.quantize_rows_np(np.asarray(k))
            vq, vs = kv_wire.quantize_rows_np(np.asarray(v))
        return kv_wire.pack(tokens[:matched],
                            self.allocator.page_size, kq, vq, ks, vs)

    def _kv_import(self, blob: bytes) -> int:
        """Decode, verify, scatter, and graft a transferred prefix
        (engine thread). Returns pages grafted (0 when everything was
        already cached locally). Raises WireError on anything corrupt,
        mismatched, or unsatisfiable — the caller degrades to plain
        recompute."""
        if self.prefix is None or self.allocator is None:
            raise ValueError(
                'KV import requires the paged prefix cache')
        blk = kv_wire.unpack(blob)
        if blk.page_size != self.allocator.page_size:
            raise kv_wire.WireError(
                f'page size {blk.page_size} != local '
                f'{self.allocator.page_size}')
        if (blk.k.shape[0] != self.config.n_layers
                or blk.k.shape[1] != self.config.n_kv_heads
                or blk.k.shape[4] != self.config.head_dim):
            raise kv_wire.WireError(
                f'KV geometry {blk.k.shape} does not match this model')
        page = blk.page_size
        n = blk.n_pages
        if len(blk.tokens) != n * page:
            raise kv_wire.WireError(
                f'{len(blk.tokens)} tokens do not fill {n} pages')
        _, have = self.prefix.peek(blk.tokens, whole=True)
        start = have // page
        need = n - start
        if need <= 0:
            return 0
        new = self.allocator.alloc_pages(need)
        if new is None:
            # Page pressure: lean on the same LRU eviction the local
            # attach path uses before giving up.
            self.prefix.evict(need - self.allocator.free_pages)
            new = self.allocator.alloc_pages(need)
        if new is None:
            raise kv_wire.WireError(
                f'page pool dry ({need} pages needed)')
        if self.cache.k_scales is not None:
            # int8 pool: the transferred bytes land verbatim —
            # byte-exact with what the donor holds.
            self.cache = paged_cache_lib.scatter_pages(
                self.cache, new, blk.k[:, :, start:], blk.v[:, :, start:],
                blk.k_scales[:, :, start:], blk.v_scales[:, :, start:])
        else:
            self.cache = paged_cache_lib.scatter_pages(
                self.cache, new,
                kv_wire.dequantize_rows_np(blk.k[:, :, start:],
                                           blk.k_scales[:, :, start:]),
                kv_wire.dequantize_rows_np(blk.v[:, :, start:],
                                           blk.v_scales[:, :, start:]))
        added = self.prefix.insert_remote(
            blk.tokens, [None] * start + list(new))
        assert added == need, (
            f'import diff went stale on the owner thread: grafted '
            f'{added} of {need}')
        return added

    # ---- internals -------------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        raise AssertionError(
            f'prompt length {n} has no bucket (max {self._buckets[-1]}) — '
            f'submit() should have rejected it')

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    @staticmethod
    def _source_tokens(req: Request) -> List[int]:
        """What prefill must cache for `req`: the prompt, plus — after a
        preemption — everything already generated (resume-by-recompute:
        the sampled token of the final resume chunk is then simply the
        NEXT new token, so the normal first-token plumbing continues
        the stream)."""
        return req.prompt_tokens + req.output_tokens

    def _do_chunk(self, slot: int) -> Optional[bool]:
        """Advance one prefilling slot by ONE chunk — NO host sync
        (a completing chunk's first token is read from its in-flight
        record once the chunk has ended). Returns True when the prompt
        is fully cached (slot joins this step's decode), False on
        progress, None when the page pool cannot cover the chunk right
        now (deferred; decode continues and finishing slots free
        pages)."""
        plan = self._prepare_chunk(slot)
        if plan is None:
            return None
        return self._dispatch_chunk_plan(plan)

    def _prepare_chunk(self, slot: int) -> Optional[_ChunkPlan]:
        """Host half of advancing one prefilling slot by ONE chunk:
        prefix-cache attach (with the defer-time rollback), page
        coverage, bucket choice, padded token block — everything
        except the device call, so the chunk can dispatch standalone
        OR fused into the decode dispatch. Returns None when the page
        pool cannot cover the chunk right now (deferred)."""
        req = self._slots[slot]
        off = self._prefilling[slot]
        source = self._source_tokens(req)
        just_attached = 0
        prev_cached = req.cached_tokens
        if (self.prefix is not None and off == 0
                and slot not in self._matched
                and self.allocator.pages_of(slot) == 0):
            self._matched.add(slot)
            # First chunk of this slot's (re-)prefill: attach the
            # longest cached page-aligned prefix and start past it.
            # Attach and chunk dispatch are one atomic host step — IF
            # the chunk defers, the attach is rolled back below, so no
            # decode ever sees shared pages in the table while the
            # device-side lengths[slot] is still 0 (the inactive-slot
            # garbage write lands at table[slot, 0], which must be the
            # sink, never a cached page).
            pages, matched = self.prefix.match(source)
            if matched:
                self.allocator.attach(slot, pages)
                self._attached_slots.add(slot)
                self._prefilling[slot] = off = matched
                req.cached_tokens = max(
                    req.cached_tokens,
                    min(matched, len(req.prompt_tokens)))
                just_attached = matched
        n = len(source)
        remaining = n - off
        bucket = self._bucket(min(remaining, self._chunk_cap))
        # A prefix-match offset is page-aligned, not cap-aligned, so
        # the rounded bucket can overshoot the cache end (e.g. off=832,
        # remaining=191 -> bucket 256 -> 1088 > max_seq_len 1024, which
        # extend would refuse FOREVER as a per-slot-ceiling failure).
        # Clamp to the largest bucket that fits, splitting the tail
        # across more chunks — the page-sized bucket always fits, and
        # only already-compiled buckets are used.
        while off + bucket > self.ecfg.max_seq_len:
            bucket = max(b for b in self._buckets if b < bucket)
        tl = min(remaining, bucket)
        if self.allocator is not None:
            ok = self._extend_pages(slot, off + bucket)
            if not ok:
                # Pool dry by STALE accounting: in-flight steps may be
                # about to free pages (finished slots). Catch up to the
                # present before declaring the chunk deferred, so page
                # decisions are identical at every pipeline depth.
                self._drain_inflight()
                ok = self._extend_pages(slot, off + bucket)
            if ok:
                # The chunk writes its whole padded bucket: every page
                # in that range must be private before the dispatch (an
                # un-CoW-able shared page defers like a dry pool).
                ok = self._unshare_write_range(slot, off, off + bucket)
            if not ok:
                if just_attached:
                    # Roll the attach back before deferring: a slot
                    # with attached pages but NO dispatched prefill has
                    # device lengths[slot] == 0, and the very next
                    # decode step would scatter its garbage K/V row
                    # into the shared page at table[slot, 0]. The retry
                    # re-runs the match (stats un-counted here).
                    self.allocator.free(slot)
                    self._attached_slots.discard(slot)
                    self._matched.discard(slot)
                    self._prefilling[slot] = 0
                    req.cached_tokens = prev_cached
                    self.prefix.hits -= 1
                    self.prefix.tokens_saved -= just_attached
                return None
            table_row = jnp.asarray(self.allocator.table()[slot])
            if self.window_alloc is not None:
                self.window_alloc.cover(slot, off, off + bucket)
                table_row = (table_row, jnp.asarray(
                    self.window_alloc.table()[slot]))
        else:
            table_row = None
        padded = np.zeros((bucket,), np.int32)
        padded[:tl] = source[off:off + tl]
        return _ChunkPlan(slot=slot, req=req, off=off, bucket=bucket,
                          tl=tl, total=n, padded=padded,
                          table_row=table_row)

    def _note_first_dispatch(self, req: Request) -> None:
        """Queue-wait boundary: the request's first chunk is about to
        dispatch (page coverage secured). Not re-stamped on preemption
        resume — the wait being measured is the scheduler's
        admission-to-service latency."""
        if req.first_dispatch_at is None:
            req.first_dispatch_at = time.time()
            wait = req.first_dispatch_at - req.submitted_at
            with self._lock:
                self._queue_waits.append(wait)
                self._sched.note_queue_wait(req, wait)
                self._stepline.note_event(
                    req.request_id, req.tenant, 'first_dispatch',
                    req.first_dispatch_at,
                    queue_wait_s=round(wait, 6))

    def _dispatch_chunk_plan(self, plan: _ChunkPlan) -> bool:
        """Standalone dispatch of a prepared chunk via the prefill
        program (no host sync). Returns True when the prompt is now
        fully cached: the chunk's own ``[token, finite]`` then starts
        its copy to the host at once and is queued as a first-token
        record, AHEAD of the decode this step dispatches behind it."""
        self._note_first_dispatch(plan.req)
        with self._stage('dispatch'):
            self._note_launch()
            if self.allocator is not None:
                self.cache, self._last_dev, first = self._prefill_chunk(
                    self.cache, self.params, jnp.int32(plan.slot),
                    plan.table_row, jnp.asarray(plan.padded),
                    jnp.int32(plan.off), jnp.int32(plan.tl),
                    self._next_key(),
                    jnp.float32(plan.req.temperature), self._last_dev)
            else:
                self.cache, self._last_dev, first = self._prefill_chunk(
                    self.cache, self.params, jnp.int32(plan.slot),
                    jnp.asarray(plan.padded), jnp.int32(plan.off),
                    jnp.int32(plan.tl), self._next_key(),
                    jnp.float32(plan.req.temperature), self._last_dev)
        with self._lock:
            self._prefill_tokens += plan.tl
            self._note_prefill_dispatched(plan)
        done = self._note_chunk_dispatched(plan)
        if done:
            first.copy_to_host_async()
            self._queue.append(
                inflight.FirstToken(first, plan.slot, plan.req))
        return done

    def _note_prefill_dispatched(self,  # holds: _lock
                                 plan: _ChunkPlan) -> None:
        """Timeline event: the chunk just dispatched was the LAST of
        the request's prompt. From here to ``first_token`` lie what
        the device still had ahead of the chunk (at depth 1 the decode
        in flight) and the chunk's own device time; the fused mixed
        step's first token waits for its whole pair besides."""
        if plan.off + plan.tl >= plan.total:
            self._stepline.note_event(
                plan.req.request_id, plan.req.tenant,
                'prefill_dispatched', time.time(), off=plan.off)

    def _note_chunk_dispatched(self, plan: _ChunkPlan) -> bool:
        """Post-dispatch bookkeeping shared by the standalone and
        fused paths: advance (or retire) the prefill frontier. True =
        the slot's prompt is fully cached."""
        off = plan.off + plan.tl
        if off < plan.total:
            self._prefilling[plan.slot] = off
            return False
        del self._prefilling[plan.slot]
        self._slot_len[plan.slot] = plan.total
        self._temps[plan.slot] = plan.req.temperature
        self._temps_dirty = True
        return True

    def _finished(self, req: Request, slot: int, token: int) -> bool:
        if self.ecfg.eos_id is not None and token == self.ecfg.eos_id:
            req.finish_reason = 'eos'
            return True
        if len(req.output_tokens) >= req.max_new_tokens:
            req.finish_reason = 'max_tokens'
            return True
        if self._slot_len[slot] + 1 >= self.ecfg.max_seq_len:
            req.finish_reason = 'cache_full'
            return True
        return False

    def _release_slot_pages(self, slot: int, req: Request,
                            prefilled_to: Optional[int] = None) -> None:
        """Give the slot's pages back — to the prefix tree when it is
        enabled (full clean pages become cached prefixes; the partial
        tail frees), to the pool otherwise. ``prefilled_to`` carries
        the prefill frontier for a slot released mid-prefill, where
        ``_slot_len`` is still 0 but [0, prefilled_to) is (or will be,
        in program order) in the cache."""
        if self.allocator is None:
            return
        if self.window_alloc is not None:
            self.window_alloc.free(slot)
        self._attached_slots.discard(slot)
        if self.prefix is None or not self.allocator.pages_of(slot):
            self.allocator.free(slot)
            return
        covered = (prefilled_to if prefilled_to is not None
                   else int(self._slot_len[slot]))
        seq = (req.prompt_tokens + req.output_tokens)[:covered]
        self.prefix.donate(seq, slot)

    def _finish(self, slot: int, req: Request) -> None:
        # Under the (reentrant) engine lock so metrics() never sees a
        # half-applied finish (slot freed but pages not yet returned).
        with self._lock:
            req.finished_at = time.time()
            if req.first_token_at is None and req.output_tokens:
                # Never report a None/0 TTFT for a request that DID
                # stream tokens (a fully-cached prompt finishing the
                # same step its first token landed).
                req.first_token_at = req.finished_at
                self._ttfts.append(req.finished_at - req.submitted_at)
                self._sched.note_first_token(
                    req, req.finished_at - req.submitted_at)
                self._sl_first_token(
                    req, req.finished_at - req.submitted_at)
            self._stepline.note_event(
                req.request_id, req.tenant, 'done',
                req.finished_at, finish_reason=req.finish_reason,
                tokens=len(req.output_tokens))
            if req.finish_reason == 'cache_full':
                # Anomaly trigger: the request was cut by cache
                # exhaustion — page pressure in the retained steps
                # explains why.
                self._note_anomaly('cache_full', {
                    'request_id': req.request_id,
                    'tenant': req.tenant, 'slot': slot})
            self._slots[slot] = None
            # Release BEFORE zeroing _slot_len: donation covers exactly
            # the positions whose K/V the pages hold, which is what
            # _slot_len still records here.
            self._release_slot_pages(slot, req)
            self._slot_len[slot] = 0
            self.cache = self._free(self.cache, jnp.int32(slot))
        req._notify()

    def _finish_queued(self, req: Request, reason: str) -> None:
        """Finish a request that never reached a slot (abandoned or
        expired while waiting). Under the engine lock."""
        req.finish_reason = reason
        req.finished_at = time.time()
        self._stepline.note_event(
            req.request_id, req.tenant, 'done', req.finished_at,
            finish_reason=reason, tokens=len(req.output_tokens))
        req._notify()

    def _finish_early(self, slot: int, req: Request, reason: str) -> None:
        """Tear an ACTIVE slot down outside the natural finish path
        (client gone / deadline passed): same page discipline as
        ``_finish`` — donate-or-free BEFORE zeroing ``_slot_len`` — plus
        mid-prefill cleanup (the ``_prefilling`` frontier is what the
        pages cover). Engine thread only: it mutates device state. Any
        in-flight pipeline steps for this slot drop their tokens via the
        stale-by-one rule (``_slots[slot] is not req``)."""
        with self._lock:
            prefilled_to = self._prefilling.pop(slot, None)
            req.finish_reason = reason
            req.finished_at = time.time()
            self._stepline.note_event(
                req.request_id, req.tenant, 'done',
                req.finished_at, finish_reason=reason,
                tokens=len(req.output_tokens))
            self._slots[slot] = None
            self._matched.discard(slot)
            self._release_slot_pages(slot, req, prefilled_to)
            self._slot_len[slot] = 0
            self.cache = self._free(self.cache, jnp.int32(slot))
        req._notify()

    def _sweep_dead_requests(self) -> None:  # holds: _lock
        """Drop queued requests whose client is gone or whose deadline
        passed — they must stop occupying admission-control queue slots
        — and finish active ones ('cancelled'/'deadline' frees the slot
        mid-decode and donates its clean pages exactly like a natural
        finish). Called from the step loop under the engine lock.
        Wall-clock gated: the multihost lockstep driver disables it
        (hosts must make identical decisions; their clocks differ)."""
        if not self.wallclock_cancel:
            return
        now = time.time()
        for r, reason in self._sched.sweep(now):
            if reason == 'cancelled':
                self._abandoned += 1
            else:
                self._expired += 1
            self._finish_queued(r, reason)
        for slot, r in enumerate(self._slots):
            if r is None:
                continue
            if r.cancelled:
                self._cancelled += 1
                self._sched.note_outcome(r, 'cancelled')
                self._finish_early(slot, r, 'cancelled')
            elif r.deadline is not None and now > r.deadline:
                self._expired += 1
                self._sched.note_outcome(r, 'deadline')
                self._finish_early(slot, r, 'deadline')

    def _preempt(self, slot: int) -> None:
        """Evict `slot` to reclaim its pages: the request goes back to
        the FRONT of the queue and resumes by recomputing
        prompt+generated (vLLM-style recompute preemption; with the
        prefix cache its donated pages make the resume re-match its own
        prefix, so the recompute shrinks to the partial tail). Output
        already streamed is kept; TTFT is not re-recorded."""
        with self._lock:
            req = self._slots[slot]
            self._slots[slot] = None
            prefilled_to = self._prefilling.pop(slot, None)
            self._release_slot_pages(slot, req, prefilled_to)
            self._slot_len[slot] = 0
            self.cache = self._free(self.cache, jnp.int32(slot))
            self._sched.requeue(req)
            self._preemptions += 1
            # Anomaly trigger: a preemption is the canonical "why was
            # this request slow" incident — the retained steps show
            # the page pressure that caused it.
            self._note_anomaly('preemption', {
                'request_id': req.request_id, 'tenant': req.tenant,
                'slot': slot,
                'tokens_recomputed': len(req.prompt_tokens)
                + len(req.output_tokens)})

    def _unshare_write_range(self, slot: int, start_tok: int,
                             end_tok: int) -> bool:
        """Copy-on-write every shared page the coming writes to
        positions [start_tok, end_tok) would touch, so no dispatch ever
        mutates a page the radix tree (or another slot) still maps.
        Returns False when a needed copy could not get a page (pool dry
        and nothing evictable) — the caller treats that exactly like an
        ``extend`` failure (defer the chunk / run the preemption
        ladder), per ``PageAllocator.cow``'s contract.

        Under the current match policy a CoW never fires — ``match``
        caps at the last full page strictly before the prompt end, so
        attached pages always sit strictly behind the write frontier —
        but the invariant is enforced mechanically here rather than
        implied by the matcher, so a future matching change (sharing
        the frontier page) degrades to a page copy instead of silent
        cross-request KV corruption."""
        if self.prefix is None or slot not in self._attached_slots:
            # Only a slot that attached cached pages can map a shared
            # page (fresh extend pages are born refcount-1 and the tree
            # never increfs a slot's private pages) — everyone else
            # skips the per-token refcount walk.
            return True
        al = self.allocator
        page = al.page_size
        first = start_tok // page
        last = (max(end_tok, start_tok + 1) - 1) // page
        for idx in range(first, min(last + 1, al.pages_of(slot))):
            if al.refcount(al.page_at(slot, idx)) <= 1:
                continue
            if not al.free_pages:
                self.prefix.evict(1)
            pair = al.cow(slot, idx)
            if pair is None:
                return False
            self.cache = self._cow(self.cache, jnp.int32(pair[0]),
                                   jnp.int32(pair[1]))
        return True

    def _extend_pages(self, slot: int, upto_tokens: int) -> bool:
        """``allocator.extend`` with the prefix cache's LRU evictor as
        the pressure valve: reclaim unreferenced cached pages (leaf
        first) only when the free stack cannot cover the growth, and
        only as many as the shortfall — BEFORE the caller escalates to
        draining the pipeline or preempting a victim."""
        if self.allocator.extend(slot, upto_tokens):
            return True
        if self.prefix is None:
            return False
        need = self.allocator.pages_needed(upto_tokens)
        if need > self.allocator.max_pages_per_slot:
            return False   # per-slot ceiling: eviction cannot help
        shortfall = (need - self.allocator.pages_of(slot)
                     - self.allocator.free_pages)
        if shortfall <= 0 or not self.prefix.evict(shortfall):
            return False
        return self.allocator.extend(slot, upto_tokens)

    def _ensure_decode_pages(self, decoding: List[int]) -> List[int]:
        """Guarantee every decoding slot owns the page its next token
        writes into, preempting the youngest other slot when the pool
        is dry. Returns the (possibly shrunk) decoding list.

        With dispatch-ahead, coverage must reach the position the
        device will have written once the in-flight steps land
        (slot_len + in-flight + 1), and any preempt/finish decision is
        made only AFTER draining the in-flight queue — stale accounting
        must never evict a victim that a pending consume was about to
        free naturally (keeps page decisions depth-invariant)."""
        decoding = list(decoding)

        def target(s: int) -> int:
            return int(self._slot_len[s]) + self._inflight_tok[s] + 1

        for slot in list(decoding):
            if slot not in decoding:
                continue   # preempted as an earlier slot's victim
            if self._slots[slot] is None:
                decoding.remove(slot)
                continue
            # The unshare runs only once coverage exists; its failure
            # (a shared page the pool cannot copy) walks the same
            # drain → preempt → cache_full ladder as a dry pool.
            while not (self._extend_pages(slot, target(slot))
                       and self._unshare_write_range(
                           slot, int(self._slot_len[slot]),
                           target(slot))):
                if self._queue:
                    # Catch up: pending consumes may free pages (and
                    # may finish THIS slot, handled by the re-checks).
                    self._drain_inflight()
                    if self._slots[slot] is None:
                        break
                    continue
                # Per-slot ceiling: no amount of preemption helps.
                if (self.allocator.pages_needed(target(slot))
                        > self.allocator.max_pages_per_slot):
                    req = self._slots[slot]
                    req.finish_reason = 'cache_full'
                    self._finish(slot, req)
                    break
                victims = [s for s, r in enumerate(self._slots)
                           if r is not None and s != slot]
                if not victims:
                    # Alone and out of pages: the pool itself is the
                    # ceiling for this request.
                    req = self._slots[slot]
                    req.finish_reason = 'cache_full'
                    self._finish(slot, req)
                    break
                with self._lock:
                    victim = self._sched.pick_victim(victims,
                                                     self._slots)
                self._preempt(victim)
                if victim in decoding:
                    decoding.remove(victim)
        # Drains above may have finished/preempted slots validated
        # earlier in the walk — only currently-decoding slots may ride
        # into the dispatch's active mask.
        if self.window_alloc is not None:
            for slot in decoding:
                if self._slots[slot] is not None:
                    self.window_alloc.cover(
                        slot, int(self._slot_len[slot]), target(slot))
        return [s for s in decoding
                if self._slots[s] is not None
                and s not in self._prefilling]

    # ---- the step --------------------------------------------------------
    def step(self) -> int:
        """Refill free slots, advance at most ``prefill_chunks_per_step``
        prefill chunks (round-robin across prefilling slots), then decode
        one token for every fully-prefilled slot. Returns the number of
        slots worked on.

        The step body runs between a counter pre-snapshot and a ring
        append of the flight recorder: the record is derived purely
        from clocks and counter deltas. The step and its stages are
        also ``jax.profiler`` annotations
        (``engine.step`` carries the record's index as ``step_num``):
        a profiler trace shows them beside the device's operations."""
        t0 = time.perf_counter()
        cpu0 = time.thread_time()   # inside the wall pair: cpu_s <= dur_s
        t_wall = time.time()
        self._sl_batch = 0
        self._sl_dev_empty = 0
        with self._lock:
            pre = (self._prefill_tokens, self._spec_drafted,
                   self._spec_accepted, self._decode_steps,
                   self._spec_steps, self._fused_steps,
                   self._decode_tokens)
            idx = self._stepline.steps.total
        with self._sl_clock.step(idx):
            worked = self._step_inner()
        cpu = time.thread_time() - cpu0
        self._sl_record(t_wall, time.perf_counter() - t0, cpu, pre)
        self._flush_stepline_dumps()
        return worked

    def wait_stage(self) -> Any:
        """Stage ``wait`` of this engine's clock: the context the step
        loop blocks for work in (``infer/server.py``). Engine thread."""
        return self._stage('wait')

    def _note_launch(self) -> None:
        """At the entry of a step-program launch, before anything is
        launched: did the device's queue run empty? ``_last_dev`` is
        the newest result of the previous launch, so it is ready only
        once all that was launched before it has run (the page frees
        and copies launched since are microseconds). ``is_ready`` asks
        the runtime and moves nothing. A result that cannot be asked
        (deleted: asking one crashes the runtime) counts as no launch
        at all."""
        after_wait = self._sl_clock.take_wait('launch') > 0.0
        prev = self._last_dev
        try:
            if prev.is_deleted():
                return
            empty = bool(prev.is_ready())
        except Exception:  # noqa: BLE001 — telemetry must never throw
            return
        with self._lock:
            self._launches += 1
            if empty:
                self._launches_dev_empty += 1
                self._sl_dev_empty = 1
                if after_wait:
                    self._launches_after_wait += 1

    def _step_inner(self) -> int:
        """The step body (see :meth:`step`).

        The lock guards only the waiting queue — prefill compiles/executes
        on-device and must not block submit() (which HTTP handlers call
        from the event loop)."""
        self._service_kv_jobs()
        with self._stage('sched'), self._lock:
            self._sweep_dead_requests()
            spec_k = self._spec_k
            for slot in range(self.ecfg.n_slots):
                if self._slots[slot] is None:
                    req = self._sched.pop_next()
                    if req is None:
                        break
                    self._slots[slot] = req   # reserve before releasing
                    self._prefilling[slot] = 0
                    self._matched.discard(slot)
                    if req.first_dispatch_at is not None:
                        # A request re-entering a slot with a dispatch
                        # already stamped is a preemption resume — the
                        # timeline shows the gap it paid.
                        self._stepline.note_event(
                            req.request_id, req.tenant, 'resume',
                            time.time(), slot=slot)
        # Chunk phase: bounded prefill work per step so decode latency
        # of active slots stays flat under prompt bursts. Chunks are
        # async dispatches (no sync), so several per step cost latency
        # only in device compute.
        just_prefilled: List[int] = []
        deferred: set = set()
        plan: Optional[_ChunkPlan] = None
        has_decode = any(r is not None and s not in self._prefilling
                         for s, r in enumerate(self._slots))
        if self._fused and has_decode and self._prefilling:
            # Fused mode with an active decode batch: exactly ONE
            # chunk rides the decode dispatch (standalone prefill
            # dispatches landing between decode dispatches are the
            # ITL stall this mode removes). The scheduler's
            # chunk-budget hook picks which prefilling slot gets the
            # fused lane; a dry pool defers the chunk — decode keeps
            # running and freeing pages, so no livelock is possible
            # while anything decodes.
            candidates = sorted(self._prefilling)
            with self._lock:
                slot = self._sched.next_prefill_slot(candidates,
                                                     self._slots)
            plan = self._prepare_chunk(slot)
        else:
            chunks_dispatched = 0
            for _ in range(self.ecfg.prefill_chunks_per_step):
                candidates = sorted(s for s in self._prefilling
                                    if s not in deferred)
                if not candidates:
                    break
                # The scheduler spends the chunk budget (fcfs: the
                # historical round-robin cursor; deadline: most urgent
                # first; wfq: rotate across tenants). Under the lock —
                # scheduler state is lock-guarded by contract.
                with self._lock:
                    slot = self._sched.next_prefill_slot(candidates,
                                                         self._slots)
                result = self._do_chunk(slot)
                if result is None:
                    # Page pool dry: stop burning chunk budget on this
                    # slot until decode frees pages.
                    deferred.add(slot)
                else:
                    chunks_dispatched += 1
                    if result:
                        just_prefilled.append(slot)
            if chunks_dispatched and has_decode:
                # Decode-ready slots waited on standalone prefill
                # dispatch(es) this step — the stall the fused mode
                # exists to remove (its gauge stays ~0 fused-on).
                with self._lock:
                    self._stall_steps += 1
        if (deferred and self.allocator is not None
                and not any(r is not None and s not in self._prefilling
                            for s, r in enumerate(self._slots))):
            # Nothing is decoding, so nothing will ever free pages on
            # its own: deferral would livelock. Preempt the youngest
            # OTHER page-holding slot in favor of the oldest deferred
            # one; a deferred request alone in the engine that still
            # can't extend has outgrown the pool itself.
            keep = min(deferred,
                       key=lambda s: self._slots[s].submitted_at)
            victims = [s for s, r in enumerate(self._slots)
                       if r is not None and s != keep
                       and self.allocator.pages_of(s) > 0]
            if victims:
                with self._lock:
                    victim = self._sched.pick_victim(victims,
                                                     self._slots)
                self._preempt(victim)
            else:
                req = self._slots[keep]
                req.finish_reason = 'cache_full'
                self._prefilling.pop(keep, None)
                self._finish(keep, req)
        # Decode phase: every fully-prefilled slot — including the ones
        # that JUST finished prefill (their first token is in _last_dev
        # and in their first-token record; they decode their second
        # token in this same step). The step dispatches ONE [2, slots]
        # pair: row 1 everyone's new token — but at pipeline_depth > 0
        # the pair read is the PREVIOUS step's, consumed only after
        # this step's decode is already dispatched, so the device never
        # waits on host bookkeeping. The first-token records are read
        # on the way to it, each as soon as its chunk has ended.
        if plan is not None:
            # A chunk is riding this step's dispatch: the fused mixed
            # program has no draft lanes, so speculation stands down
            # for the step (prefill progress outranks drafting — the
            # opportunistic contract; outputs are unchanged either
            # way, only step counts move).
            spec_k = 0
        if spec_k:
            # Draft eligibility is knowable from host slot state alone
            # (greedy, opted in, fully prefilled, not this step's
            # fresh prefill) — and draining can only ever REMOVE
            # eligibility (a consume may finish a slot), never create
            # it. So a spec-enabled engine serving only sampled or
            # opted-out traffic skips both the drain and the draft
            # pass and keeps the full dispatch-ahead overlap — exactly
            # the spec-off step.
            fresh = set(just_prefilled)
            eligible = [s for s in range(self.ecfg.n_slots)
                        if self._spec_eligible(s, fresh)]
            if not eligible:
                spec_k = 0
            elif self._queue and not any(
                    self._drafter.propose(
                        drafter_lib.cached_context(
                            self._slots[s].prompt_tokens,
                            self._slots[s].output_tokens,
                            self._slots[s].draft_memo),
                        1, memo=self._slots[s].draft_memo)
                    for s in eligible):
                # Eligible slots, but no trailing n-gram matches the
                # (stale-by-one) host context: nobody would draft, so
                # skip the drain too — greedy-but-non-repetitive
                # traffic keeps the dispatch-ahead overlap instead of
                # paying a device sync per step for nothing. A match
                # that only the post-drain token would create just
                # starts speculating one step later (the opportunistic
                # contract); the memo index these probes build is the
                # same one the real draft pass uses.
                spec_k = 0
        if spec_k and self._queue:
            # Speculation: the drafter continues the host-known token
            # sequence, but an in-flight step is about to append to it
            # — catch up BEFORE drafting (and before the decoding list
            # is built, so drain-side finishes are seen). The dispatch
            # below still leaves up to _depth steps in flight, so the
            # async-readback overlap survives; only the consume moved
            # from after the dispatch to before the next draft.
            # Timed as decode work: the consume's sync wait prices the
            # effective tokens/sec that Retry-After estimates divide
            # by.
            t0 = time.perf_counter()
            self._drain_inflight()
            with self._lock:
                self._decode_time += time.perf_counter() - t0
        decoding = [s for s, r in enumerate(self._slots)
                    if r is not None and s not in self._prefilling]
        if self.allocator is not None and decoding:
            decoding = self._ensure_decode_pages(decoding)
        if plan is not None and (
                self._slots[plan.slot] is not plan.req
                or self._prefilling.get(plan.slot) != plan.off):
            # The chunk's slot was preempted while decode page
            # pressure resolved: the request is back in the queue and
            # will re-prefill from scratch — drop the stale plan.
            plan = None
        if not decoding and not self._queue and plan is None:
            return len(self._prefilling)
        t0 = time.perf_counter()
        if plan is not None:
            if decoding:
                self._dispatch_mixed(decoding, plan)
            else:
                # The decode batch evaporated (page-pressure drains
                # finished every decoder): the prepared chunk goes out
                # standalone, and a completed prompt's first token is
                # read by its own record in this step's drain below.
                self._dispatch_chunk_plan(plan)
        elif decoding:
            drafts = (self._build_drafts(decoding, just_prefilled,
                                         spec_k) if spec_k else None)
            if drafts is not None:
                self._dispatch_verify(decoding, *drafts)
            else:
                # No drafts this step (spec off, sampled slots, or no
                # n-gram matched): the plain decode program is the
                # cheaper dispatch — a draftless verify would pay
                # spec_k wasted lanes per slot.
                self._dispatch_decode(decoding)
        # Keep at most _depth steps in flight; with nothing newly
        # dispatched there is no overlap left to win — drain fully so
        # finished requests surface and idle() can flip.
        self._consume_to(self._depth if decoding else 0)
        with self._lock:
            self._decode_time += time.perf_counter() - t0
        return len(decoding) + len(self._prefilling)

    def _refresh_dispatch_state(self, decoding: List[int]) -> None:
        """Re-upload the per-token decode operands behind their dirty
        flags (temps, active mask, paged block table) — the shared
        preamble of the decode AND verify dispatchers, factored so an
        invalidation fix can never land on one path and miss the
        other."""
        if self._temps_dirty or self._temps_dev is None:
            self._temps_dev = jnp.asarray(self._temps)
            self._temps_dirty = False
        key = tuple(decoding)
        if key != self._active_key or self._active_dev is None:
            active_mask = np.zeros((self.ecfg.n_slots,), np.bool_)
            active_mask[decoding] = True
            self._active_dev = jnp.asarray(active_mask)
            self._active_key = key
        if self.allocator is not None:
            version = self.allocator.version
            if self.window_alloc is not None:
                version = (version, self.window_alloc.version)
            if self._table_version != version:
                self._table_dev = jnp.asarray(self.allocator.table())
                if self.window_alloc is not None:
                    self._table_dev = (self._table_dev, jnp.asarray(
                        self.window_alloc.table()))
                self._table_version = version

    def _dispatch_decode(self, decoding: List[int]) -> None:
        """Dispatch one decode step (no host sync) and start its pair's
        device→host copy; the result is consumed by a later
        ``_consume_one``. Decode N+1 depends only on ``_last_dev`` and
        the cache — both device-resident — so it never waits for the
        host to have READ step N. A slot that just finished prefill
        rides as any other lane: the pair carries its SECOND token, its
        first is in the record queued ahead of this one."""
        with self._stage('dispatch'):
            self._note_launch()
            self._refresh_dispatch_state(decoding)
            if self.allocator is not None:
                pair, self.cache = self._decode(
                    self.cache, self.params, self._table_dev,
                    self._last_dev, self._next_key(), self._temps_dev,
                    self._active_dev)
            else:
                pair, self.cache = self._decode(
                    self.cache, self.params, self._last_dev,
                    self._next_key(), self._temps_dev,
                    self._active_dev)
            self._last_dev = pair[1]
            # Overlap the readback with everything that follows: by
            # consume time the bytes are (usually) already on the host.
            pair.copy_to_host_async()
        self._sl_batch = len(decoding)
        with self._lock:
            # Under the lock so metrics()' tokens_in_flight sum never
            # reads a half-applied increment batch (consume decrements
            # under the lock already; the RLock makes this free on the
            # engine thread), and tokens_per_step never divides by a
            # step count the token counter hasn't caught up with.
            self._decode_steps += 1
            for s in decoding:
                self._inflight_tok[s] += 1
        self._queue.append(inflight.StepPair(
            pair, [(s, self._slots[s]) for s in decoding]))

    def _dispatch_mixed(self, decoding: List[int],
                        plan: _ChunkPlan) -> None:
        """Dispatch ONE fused mixed step (no host sync): the plan's
        prefill chunk AND the decode batch in a single device program
        — the weights stream once for both, and no standalone prefill
        dispatch sits between decode dispatches. The [2, slots] pair
        rides the in-flight queue exactly like a decode pair; a chunk
        that completes its prompt surfaces its first token through
        pair row 0 (the record's ``prefilled``: the token and the
        decode are one program's result, so there is nothing earlier
        to read, and this is the one path that still reads row 0) and
        joins the NEXT step's decode — one extra step, zero
        token-sequence difference (greedy outputs are gated
        bit-identical fused on vs off)."""
        with self._stage('dispatch'):
            self._note_launch()
            self._refresh_dispatch_state(decoding)
            self._note_first_dispatch(plan.req)
            chunk_key = self._next_key()
            dec_key = self._next_key()
            if self.allocator is not None:
                pair, self.cache = self._mixed(
                    self.cache, self.params, jnp.int32(plan.slot),
                    plan.table_row, jnp.asarray(plan.padded),
                    jnp.int32(plan.off), jnp.int32(plan.tl), chunk_key,
                    jnp.float32(plan.req.temperature),
                    self._table_dev, self._last_dev, dec_key,
                    self._temps_dev, self._active_dev)
            else:
                pair, self.cache = self._mixed(
                    self.cache, self.params, jnp.int32(plan.slot),
                    jnp.asarray(plan.padded), jnp.int32(plan.off),
                    jnp.int32(plan.tl), chunk_key,
                    jnp.float32(plan.req.temperature), self._last_dev,
                    dec_key, self._temps_dev, self._active_dev)
            self._last_dev = pair[1]
            pair.copy_to_host_async()
        self._sl_batch = len(decoding)
        with self._lock:
            self._decode_steps += 1
            self._fused_steps += 1
            self._prefill_tokens += plan.tl
            self._note_prefill_dispatched(plan)
            for s in decoding:
                self._inflight_tok[s] += 1
        completes = self._note_chunk_dispatched(plan)
        self._queue.append(inflight.StepPair(
            pair, [(s, self._slots[s]) for s in decoding],
            prefilled=[(plan.slot, plan.req)] if completes else ()))

    def _spec_eligible(self, s: int, fresh: set) -> bool:
        """May slot ``s`` draft this step? Greedy, opted in, fully
        prefilled, and not one of this step's fresh prefills (their
        first token's record is not read yet, so the host cannot
        continue the sequence). ONE definition, shared by step()'s
        skip-the-drain gate and ``_build_drafts`` — an eligibility
        change must reach both or speculation silently diverges from
        the gate. Engine thread only."""
        r = self._slots[s]
        return (r is not None and s not in self._prefilling
                and s not in fresh and r.temperature == 0 and r.spec)

    def _build_drafts(self, decoding: List[int],
                      just_prefilled: List[int],
                      spec_k: int) -> Optional[tuple]:
        """Prompt-lookup drafts for this step's decoding slots.

        Returns ``(draft_mat [slots, spec_k], draft_lens [slots])``
        int32 (zero-padded; draft_lens is the static-pad active mask
        the verify program honors), or None when nobody drafted — the
        caller then dispatches the plain decode program. A slot drafts
        only when it is greedy, opted in, NOT just-prefilled (its
        first token's record is not read yet, so the host cannot
        continue the sequence), within the scheduler's per-step budget
        (wfq caps over-share tenants), short enough of the cache end
        that every drafted position fits, and — paged — coverable
        without evicting cached prefixes or preempting anyone
        (speculation is opportunistic: a dry pool trims the draft,
        never the workload)."""
        lens = np.zeros((self.ecfg.n_slots,), np.int32)
        mat = np.zeros((self.ecfg.n_slots, spec_k), np.int32)
        fresh = set(just_prefilled)
        eligible = [s for s in decoding
                    if self._spec_eligible(s, fresh)]
        if not eligible:
            return None
        with self._lock:
            # One lock round-trip for the whole step, not one per slot
            # — the budgets depend only on scheduler state.
            budgets = {s: self._sched.spec_budget(self._slots[s],
                                                  spec_k)
                       for s in eligible}
        any_draft = False
        for s in eligible:
            req = self._slots[s]
            budget = min(
                int(budgets[s]), spec_k,
                # Every drafted position must sit strictly inside the
                # cache: the run writes [len, len+draft_len] and the
                # corrected token still needs a writable position.
                self.ecfg.max_seq_len - 2 - int(self._slot_len[s]),
                # Drafting past the request's remaining token budget
                # wastes lanes/pages: the finish check would drop the
                # surplus anyway.
                req.max_new_tokens - len(req.output_tokens) - 1)
            if budget <= 0:
                continue
            prop = self._drafter.propose(
                drafter_lib.cached_context(req.prompt_tokens,
                                           req.output_tokens,
                                           req.draft_memo),
                budget, memo=req.draft_memo)
            if prop and self.allocator is not None:
                base = int(self._slot_len[s])
                if not self.allocator.extend(s, base + len(prop) + 1):
                    covered = (self.allocator.pages_of(s)
                               * self.allocator.page_size)
                    prop = prop[:max(0, covered - base - 1)]
                if prop and not self._unshare_write_range(
                        s, base, base + len(prop) + 1):
                    prop = []
            if not prop:
                continue
            lens[s] = len(prop)
            mat[s, :len(prop)] = prop
            any_draft = True
        return (mat, lens) if any_draft else None

    def _dispatch_verify(self, decoding: List[int],
                         draft_mat: 'np.ndarray',
                         draft_lens: 'np.ndarray') -> None:
        """Dispatch one fused verify step (no host sync): the draft
        run's K/V writes, every candidate's logits, exact-greedy
        acceptance AND the device-side state advance (lengths +=
        accepted+1, the corrected token into ``_last_dev``) are one
        program — the device never waits for a host accept/reject.
        The [spec_k+3, slots] pair rides the in-flight queue exactly
        like a decode pair; consume applies host bookkeeping per
        emitted token and rolls rejected pages back."""
        with self._stage('dispatch'):
            self._note_launch()
            self._refresh_dispatch_state(decoding)
            drafts_dev = jnp.asarray(draft_mat)
            lens_dev = jnp.asarray(draft_lens)
            if self.allocator is not None:
                pair, self._last_dev, self.cache = self._verify(
                    self.cache, self.params, self._table_dev,
                    self._last_dev, drafts_dev, lens_dev,
                    self._next_key(), self._temps_dev,
                    self._active_dev)
            else:
                pair, self._last_dev, self.cache = self._verify(
                    self.cache, self.params, self._last_dev,
                    drafts_dev, lens_dev, self._next_key(),
                    self._temps_dev, self._active_dev)
            pair.copy_to_host_async()
        self._sl_batch = len(decoding)
        with self._lock:
            self._decode_steps += 1
            self._spec_steps += 1
            for s in decoding:
                self._inflight_tok[s] += int(draft_lens[s]) + 1
        self._queue.append(inflight.StepPair(
            pair,
            [(s, self._slots[s], int(draft_lens[s]))
             for s in decoding],
            spec_r=draft_mat.shape[1] + 1))

    def integrity_suspect(self) -> bool:
        """One-way corruption verdict (the /health + admission read).
        Lock-free on purpose: a GIL-atomic bool read, one stale step
        tolerated — the same contract as the server's ready/dead
        flags."""
        return self._integrity_suspect

    def output_digest(self) -> int:
        """Order-independent-free digest of live decode state: a
        stable CRC over each active slot's (request id, output
        tokens), slot-ordered. The multihost lockstep driver
        all-gathers this each tick and fails the slice loudly on any
        mismatch (a desynced host is SDC at slice scope — diverged
        tokens must never stream). zlib.crc32, never builtin hash()
        (per-process salted)."""
        with self._lock:
            parts = []
            for slot, req in enumerate(self._slots):
                if req is None:
                    continue
                parts.append(f'{slot}:{req.request_id}:'
                             f'{",".join(map(str, req.output_tokens))}')
        return zlib.crc32(';'.join(parts).encode())

    def set_pipeline_depth(self, depth: int) -> None:
        """Change the dispatch-ahead depth at runtime. The multihost
        lockstep driver pins 0: its tick protocol requires every host
        to observe identical request state after each tick."""
        self._depth = max(0, int(depth))
        self._consume_to(self._depth)

    def set_wallclock_cancel(self, enabled: bool) -> None:
        """Enable/disable the deadline + client-cancel sweeps. The
        multihost lockstep driver disables them (same reason it pins
        pipeline_depth 0): the sweeps read the local wall clock, and
        every host must reach identical request state each tick."""
        self.wallclock_cancel = bool(enabled)

    def set_spec_k(self, k: int) -> None:
        """Runtime draft-width knob (0 = off). Each distinct k>0 is
        its own verify program shape (drafts are [slots, k]); greedy
        outputs are bit-identical at every k. Raises when the lockstep
        driver pinned speculation off — enabling it there would let
        hosts draft from host-local state and silently diverge."""
        k = max(0, int(k))
        with self._lock:
            if k > 0 and self._spec_pinned:
                raise RuntimeError(
                    'speculative decoding is pinned off on the '
                    'multihost lockstep path: the tick spec does not '
                    'carry draft tokens, so host-local drafts would '
                    'diverge the replicas')
            self._spec_k = k

    def pin_spec_off(self) -> None:
        """Multihost lockstep: force spec_k=0 and refuse re-enabling
        (like the pipeline_depth=0 pin) until the tick spec carries
        draft tokens."""
        with self._lock:
            self._spec_k = 0
            self._spec_pinned = True

    def set_scheduler(self, name: str,
                      tenant_weights=None) -> None:
        """Swap the scheduling policy at runtime (an ops knob — the
        same engine, compiled programs and KV state serve on).
        Queued requests migrate in the OLD policy's service order;
        per-tenant windows/counters restart with the new policy."""
        cfg = sched_lib.SchedulerConfig(
            max_queue_requests=self.ecfg.max_queue_requests,
            max_queue_tokens=self.ecfg.max_queue_tokens,
            tenant_weights=(tenant_weights
                            if tenant_weights is not None
                            else self.ecfg.tenant_weights))
        with self._lock:
            new = sched_lib.make(name, cfg)
            old = self._sched
            while True:
                req = old.pop_next()
                if req is None:
                    break
                new.enqueue(req)
            self._sched = new

    def set_tenant_weights(self, weights) -> None:
        """Update wfq weights mid-flight (queued work keeps its
        position; future decisions use the new weights)."""
        with self._lock:
            self._sched.set_tenant_weights(weights)

    def sched_snapshot(self) -> Dict[str, Any]:
        """Locked export of the scheduler's per-tenant raw stats —
        the EnginePool merge path (same reason as ``ttft_window``:
        cross-thread aggregators must never iterate live deques)."""
        with self._lock:
            return self._sched.snapshot()

    # ---- flight recorder -------------------------------------------------
    def _sl_first_token(self, req: Request,  # holds: _lock
                        ttft: float, early: bool = False) -> None:
        """Timeline event + the TTFT-SLO anomaly trigger, at the one
        moment TTFT becomes known. ``early``: read from the chunk's
        own record (``inflight.FirstToken``), not with a step pair."""
        self._first_tokens += 1
        self._first_tokens_early += early
        self._stepline.note_event(
            req.request_id, req.tenant, 'first_token',
            req.first_token_at, ttft_s=round(ttft, 6), early=int(early))
        slo = self.ecfg.ttft_slo_s
        if slo is not None and ttft > slo:
            self._note_anomaly('ttft_slo', {
                'request_id': req.request_id, 'tenant': req.tenant,
                'ttft_s': round(ttft, 6), 'slo_s': slo})

    def note_lifecycle_event(self, event: str,
                             t: Optional[float] = None,
                             **detail: Any) -> None:
        """Stamp a replica-lifecycle milestone (cold-start timeline:
        ``coldstart.weights_loaded`` / ``coldstart.compiled`` / ...)
        into the flight-recorder event ring, where it interleaves with
        per-request timelines on the same wall clock — `sky-tpu
        profile` and the span dumps see exactly when the replica
        became serviceable relative to its first requests. Request id
        -1 keys the pseudo-timeline (real ids start at 1)."""
        with self._lock:
            self._stepline.note_event(-1, '_lifecycle', event,
                                      t if t is not None else time.time(),
                                      **detail)

    def note_request_event(self, req: Request, event: str) -> None:
        """Stamp a front-end moment of ``req`` (the server's
        ``first_flush``: its first token line has left the handler)
        onto the request's timeline, now. One brief lock take a
        request, as ``submit`` has; never on the per-token path."""
        with self._lock:
            self._stepline.note_event(req.request_id, req.tenant,
                                      event, time.time())

    def _note_anomaly(self, trigger: str,  # holds: _lock
                      detail: Dict[str, Any]) -> None:
        """Record the anomaly in the event ring and queue a ring dump
        (rate-limited per trigger kind). The sqlite write happens on
        the stepline writer thread strictly AFTER the engine lock is
        released (`_flush_stepline_dumps`) — nothing blocks, and the
        engine lock never nests another lock."""
        now = time.time()
        detail = dict(detail, t=now,
                      step_idx=self._stepline.steps.total)
        self._stepline.note_event(
            int(detail.get('request_id') or 0),
            str(detail.get('tenant') or ''), trigger, now,
            **{k: v for k, v in detail.items()
               if k not in ('request_id', 'tenant', 't')})
        if self._stepline.should_dump(trigger, now):
            self._pending_dumps.append((trigger, detail))

    def _flush_stepline_dumps(self) -> None:
        """Hand queued anomaly dumps to the background writer. Called
        OUTSIDE the engine lock (step()/submit() tails): the ring
        snapshot is copied under the lock; the enqueue — which takes
        the writer's own condition — runs strictly after release."""
        with self._lock:
            if not self._pending_dumps:
                return
            pending = self._pending_dumps
            self._pending_dumps = []
            raw = self._stepline.raw()   # O(n) pointer copy only
        # The O(ring) per-record dict rendering happens on the WRITER
        # thread (raw()'s records are write-once, safe to share): the
        # step loop / HTTP event loop pays only the pointer copy.

        def _render(pending=pending, raw=raw):
            snap = stepline_lib.render_snapshot(raw)
            spans = []
            for trigger, detail in pending:
                spans.extend(
                    stepline_lib.dump_spans(trigger, detail, snap))
            return spans

        stepline_lib.enqueue_dump(_render)

    def _sl_record(self, t_wall: float, dur: float, cpu: float,
                   pre: tuple) -> None:
        """Classify and append this step's record from counter deltas
        (recorder on only; pure observation — no scheduling state is
        read that the step loop acts on)."""
        (pre_pref, pre_drafted, pre_accepted, pre_steps, pre_spec,
         pre_fused, pre_tok) = pre
        acc = self._sl_clock.acc
        with self._lock:
            d_disp = self._decode_steps - pre_steps
            d_chunk = self._prefill_tokens - pre_pref
            d_tok = self._decode_tokens - pre_tok
            if d_disp:
                kind = ('mixed' if self._fused_steps - pre_fused
                        else 'verify' if self._spec_steps - pre_spec
                        else 'decode')
            elif d_chunk:
                kind = 'prefill'
            elif d_tok or acc['readback'] or acc['drain']:
                # Consumes only: the step drained in-flight results /
                # freed finishing slots without dispatching new work.
                kind = 'free'
            else:
                return   # pure idle tick: not worth a ring slot
            depth = self._sched.pending()
            tenant_depths = None
            # Per-tenant decomposition is bounded: beyond this depth
            # the O(queue) walk would tax every step exactly when the
            # engine is most loaded — the record keeps the total, and
            # the per-tenant split is still in metrics()['tenants'].
            if 0 < depth <= 512:
                td: Dict[str, int] = {}
                for r in self._sched.queued_requests():
                    td[r.tenant] = td.get(r.tenant, 0) + 1
                tenant_depths = td
            self._stepline.note_step(stepline_lib.StepRecord(
                idx=self._stepline.steps.total,
                t=t_wall, dur_s=dur, kind=kind,
                dispatch_s=acc['dispatch'],
                drain_s=acc['drain'],
                readback_s=acc['readback'],
                sched_s=acc['sched'],
                cpu_s=cpu,
                wait_s=self._sl_clock.take_wait('record'),
                dev_empty=self._sl_dev_empty,
                batch=self._sl_batch,
                chunk_tokens=d_chunk,
                prefilling=len(self._prefilling),
                spec_drafted=self._spec_drafted - pre_drafted,
                spec_accepted=self._spec_accepted - pre_accepted,
                pages_free=(self.allocator.free_pages
                            if self.allocator is not None else -1),
                prefix_evictions=(self.prefix.evictions
                                  if self.prefix is not None else 0),
                preemptions=self._preemptions,
                queue_depth=depth,
                tenant_depths=tenant_depths))

    def stepline_snapshot(self) -> Dict[str, Any]:
        """Locked copy of the flight-recorder rings — the
        ``GET /debug/stepline`` payload (the ``ttft_window`` snapshot
        contract: HTTP readers never touch the live rings)."""
        with self._lock:
            raw = self._stepline.raw()   # O(n) pointer copy only
        snap = stepline_lib.render_snapshot(raw)
        snap['enabled'] = True
        snap['ttft_slo_s'] = self.ecfg.ttft_slo_s
        return snap

    def stepline_summary(self) -> Dict[str, Any]:
        """Aggregate stage breakdown over the retained window. The
        summarize math runs OUTSIDE the lock on a snapshot copy."""
        with self._lock:
            recs = self._stepline.steps.snapshot()
        out = stepline_lib.summarize(recs)
        out['enabled'] = True
        return out

    def idle(self) -> bool:
        with self._lock:
            return (not self._sched.pending()
                    and all(r is None for r in self._slots)
                    and not self._queue)

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        for _ in range(max_steps):
            if self.idle():
                return
            self.step()

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: Optional[int] = None,
                 temperature: float = 0.0) -> List[Request]:
        """Batch convenience: submit all, run to completion."""
        reqs = [self.submit(p, max_new_tokens, temperature)
                for p in prompts]
        self.run_until_idle()
        return reqs

    # ---- metrics ---------------------------------------------------------
    def ttft_window(self) -> List[float]:
        """Snapshot of the recent-TTFT window, taken under the engine
        lock. The accessor exists so cross-thread aggregators
        (EnginePool.metrics, called from HTTP threads) never iterate
        the live deque while the consume path appends to it — the
        first genuine SKY-LOCK finding of the lint bring-up."""
        with self._lock:
            return list(self._ttfts)

    def queue_wait_window(self) -> List[float]:
        """Locked snapshot of the recent queue-wait window (same
        contract as ``ttft_window``)."""
        with self._lock:
            return list(self._queue_waits)

    def _metrics_snapshot(self) -> tuple:
        """Raw counter/window snapshot taken under the engine lock —
        the data half of :meth:`metrics`, hoisted out of it so
        SKY-REGISTRY's key scan sees only EMITTED metric names (the
        accumulator keys below are internal, same rule as
        sched/base._merge_snapshots). Returns ``(ttfts, waits,
        sched_snapshot, counters, prefix_stats)``."""
        with self._lock:
            counters = dict(
                decode_steps=self._decode_steps,
                decode_tokens=self._decode_tokens,
                decode_time=self._decode_time,
                prefill_tokens=self._prefill_tokens,
                fused_steps=self._fused_steps,
                stall_steps=self._stall_steps,
                spec_k=self._spec_k,
                spec_steps=self._spec_steps,
                spec_slot_steps=self._spec_slot_steps,
                spec_drafted=self._spec_drafted,
                spec_accepted=self._spec_accepted,
                spec_emitted=self._spec_emitted,
                scheduler=self._sched.name,
                num_waiting=self._sched.pending(),
                queued_tokens=self._sched.queued_tokens(),
                num_active=sum(
                    1 for r in self._slots if r is not None),
                abandoned=self._abandoned,
                expired=self._expired,
                cancelled=self._cancelled,
                preemptions=self._preemptions,
                # Summed from the per-slot counters, NOT by iterating
                # _queue: the engine thread appends/pops the deque
                # outside this lock, and CPython raises on a deque
                # mutated mid-iteration.
                tokens_in_flight=sum(self._inflight_tok),
                pages_free=(self.allocator.free_pages
                            if self.allocator is not None else 0),
                stepline_steps=self._stepline.steps.total,
                stepline_dumps=self._stepline.dumps,
                sdc_events=self._sdc_events,
                integrity_suspect=self._integrity_suspect,
                kv_transfers=self._kv_transfers,
                kv_bytes=self._kv_transfer_bytes,
                kv_failures=self._kv_transfer_failures,
                kv_window=list(self._kv_transfer_window),
                launches=self._launches,
                launches_dev_empty=self._launches_dev_empty,
                launches_after_wait=self._launches_after_wait,
                first_tokens=self._first_tokens,
                first_tokens_early=self._first_tokens_early,
                model_counters=dict(self._model_counters))
            return (list(self._ttfts), list(self._queue_waits),
                    self._sched.snapshot(), counters,
                    self.prefix.stats() if self.prefix is not None
                    else {})

    def metrics(self) -> Dict[str, Any]:
        # Snapshot RAW state under the engine lock
        # (_metrics_snapshot), derive everything else outside it.
        # With the overlapped loop, counters (_decode_tokens, _ttfts,
        # pages_free) are written one step behind the in-flight
        # dispatch by the consume path — the lock keeps /metrics (and
        # the LB reading it) from seeing a half-applied consume. But
        # the O(n log n) percentile sorts (TTFT/queue-wait windows,
        # the per-tenant aggregate_stats merge) must NOT run under
        # it: every poll would stall the step loop for the sort's
        # duration (the ttft_window snapshot contract, applied to the
        # engine's own poll path).
        (ttfts_raw, waits_raw, sched_snap, c,
         prefix_stats) = self._metrics_snapshot()
        ttfts = sorted(ttfts_raw)
        p50 = ttfts[len(ttfts) // 2] if ttfts else None
        waits = sorted(waits_raw)
        kvw = sorted(c['kv_window'])
        return {
            'decode_steps': c['decode_steps'],
            'decode_tokens': c['decode_tokens'],
            'decode_tokens_per_sec': (
                c['decode_tokens'] / c['decode_time']
                if c['decode_time'] else 0.0),
            # Emitted tokens per dispatched step (batch-wide:
            # ~active slots without speculation; accepted runs
            # multiply it by the mean accepted length).
            'tokens_per_step': (round(
                c['decode_tokens'] / c['decode_steps'], 4)
                if c['decode_steps'] else None),
            # Prefill-stall decomposition (docs/serving.md "Fused
            # mixed steps"): prompt tokens dispatched into chunks,
            # how many rode a fused dispatch, and how often an
            # active decode batch waited on a STANDALONE prefill
            # dispatch instead (~0 with fused_prefill on).
            'prefill_tokens': c['prefill_tokens'],
            'prefill_tokens_per_step': (round(
                c['prefill_tokens'] / c['decode_steps'], 4)
                if c['decode_steps'] else None),
            'fused_steps': c['fused_steps'],
            'decode_stall_steps': c['stall_steps'],
            **({'spec_k': c['spec_k'],
                'spec_steps': c['spec_steps'],
                'spec_slot_steps': c['spec_slot_steps'],
                'spec_drafted_tokens': c['spec_drafted'],
                'spec_accepted_tokens': c['spec_accepted'],
                'spec_emitted_tokens': c['spec_emitted'],
                'spec_accept_rate': (round(
                    c['spec_accepted'] / c['spec_drafted'], 4)
                    if c['spec_drafted'] else 0.0),
                'accepted_len_mean': (round(
                    c['spec_emitted'] / c['spec_slot_steps'], 4)
                    if c['spec_slot_steps'] else None)}
               if (c['spec_k'] or c['spec_steps']) else {}),
            'ttft_p50_s': p50,
            # TTFT decomposition: submit → first chunk dispatch
            # (the scheduler's share), apart from prefill compute.
            'queue_wait_p50_ms': (round(
                waits[len(waits) // 2] * 1e3, 3) if waits
                else None),
            'queue_wait_p99_ms': (round(
                waits[min(len(waits) - 1,
                          int(len(waits) * 0.99))] * 1e3, 3)
                if waits else None),
            'scheduler': c['scheduler'],
            'num_waiting': c['num_waiting'],
            'queued_tokens': c['queued_tokens'],
            # Per-tenant percentile merge from the LOCKED raw
            # snapshot, computed outside the lock (the new per-tenant
            # windows follow the same contract as the engine ones).
            'tenants': sched_lib.aggregate_stats(
                [sched_snap], c['decode_time']),
            'num_active': c['num_active'],
            'requests_abandoned': c['abandoned'],
            'requests_expired': c['expired'],
            'requests_cancelled': c['cancelled'],
            'pipeline_depth': self._depth,
            'tokens_in_flight': c['tokens_in_flight'],
            # Flight recorder: total steps recorded (monotonic; the
            # ring keeps the last `stepline_cap`) and anomaly dumps
            # TRIGGERED (the store write is fail-open + bounded, so
            # `sky-tpu profile` may list fewer after a storm).
            'stepline_steps': c['stepline_steps'],
            'stepline_dumps': c['stepline_dumps'],
            # The engine thread between steps: seconds its loop has
            # waited for work (an open wait counted so far). Then
            # step-program launches, those that found the device's
            # queue empty, and of those the first launch after a
            # wait, which finds it empty by definition.
            'engine_wait_s': round(self._sl_clock.waited_s(), 6),
            'launches': c['launches'],
            'launches_device_empty': c['launches_dev_empty'],
            'launches_after_wait': c['launches_after_wait'],
            # First tokens stamped, and of those the ones read as soon
            # as the prompt's last chunk had ended (every one but the
            # fused mixed step's, and a request finished before any).
            'first_token_total': c['first_tokens'],
            'first_token_early_total': c['first_tokens_early'],
            # Data-integrity plane (docs/robustness.md "Data
            # integrity"): on-device sentinel hits and the one-way
            # corruption verdict ('ok'/'suspect' — a state set in the
            # Prometheus rendering, never a numeric sample).
            'sdc_events_total': c['sdc_events'],
            'integrity': ('suspect' if c['integrity_suspect']
                          else 'ok'),
            # Fleet KV streaming (docs/serving.md "Disaggregated
            # prefill/decode"): transfers this replica took part in
            # (exports served + imports applied), wire bytes moved,
            # transfers that died anywhere on the pull path, and the
            # p99 transfer wall time over a recent window.
            'kv_transfers_total': c['kv_transfers'],
            'kv_transfer_bytes': c['kv_bytes'],
            'kv_transfer_failures': c['kv_failures'],
            'kv_transfer_p99_s': (round(
                kvw[min(len(kvw) - 1, int(len(kvw) * 0.99))], 6)
                if kvw else None),
            **({'paged': True,
                'page_size': self.allocator.page_size,
                'pages_total': self.allocator.n_pages,
                'pages_free': c['pages_free'],
                'preemptions': c['preemptions'],
                # Page value dtype + per-(k+v)-page HBM bytes
                # across all layers (int8 incl. its fp32 row
                # scales) — the denominator behind the "~2x
                # resident pages per HBM byte" claim.
                'kv_dtype': self.ecfg.kv_dtype,
                'kv_page_bytes': self.cache.page_bytes}
               if self.allocator is not None else {}),
            # A model with recurrent layers (infer/state_cache.py):
            # the HBM its per-slot state takes and the slots that hold
            # a live one; then the decode program's own counts
            # (model.HYBRID_STEP_STATS), summed over decode steps.
            **({'state_bytes': self.cache.state_bytes,
                'state_slots': c['num_active']}
               if self._state_spec is not None else {}),
            # Window layers (paged_cache.WindowAllocator): their pool,
            # and the rows live slots hold of it right now.
            **({'window_pages_total': self.window_alloc.n_pages,
                'window_rows_held': self.window_alloc.rows_held(),
                'window_bytes': self.cache.window_bytes}
               if self.window_alloc is not None else {}),
            **c['model_counters'],
            **prefix_stats,
        }

    def compiled_counts(self) -> Dict[str, int]:
        """Distinct compiled programs per jitted entry point — the
        recompile-stability guard: slot refill, dirty-flag re-uploads,
        and dispatch-ahead must never introduce new shapes (prefill
        compiles once per bucket; decode and free exactly once)."""
        def n(fn) -> int:
            return int(fn._cache_size())
        with self._lock:
            spec_on = bool(self._spec_k or self._spec_steps)
        return {'prefill': n(self._prefill_chunk),
                'decode': n(self._decode),
                'free': n(self._free),
                # Fused mode adds one mixed program per CHUNK BUCKET
                # (the chunk shape is the only varying operand — the
                # decode half is static), mirroring the prefill
                # ladder; fused-off engines never compile (or report)
                # it.
                **({'mixed': n(self._mixed)} if self._fused else {}),
                # Prefix cache adds exactly ONE potential program (the
                # CoW page copy) which stays at 0 compiles unless a CoW
                # actually fires — prefill-from-offset reuses the
                # existing chunk buckets (offset is a traced scalar).
                **({'cow': n(self._cow)} if self.prefix is not None
                   else {}),
                # Speculation adds exactly ONE program per draft width
                # (drafts are [slots, spec_k], static pad + draft_len
                # mask — no per-draft-length shapes): verify=1 in
                # steady state.
                **({'verify': n(self._verify)} if spec_on else {})}


class EnginePool:
    """Length-routed pool of engines — two-tier KV for long context.

    The dense per-slot cache prices EVERY slot at the pool's longest
    sequence; serving 16 slots at 16k would cost 16x16k of KV HBM even
    though most requests are short. A pool routes each request to the
    smallest engine whose cache fits its prompt, so HBM is
    sum(slots_i * seq_i) — e.g. 16x2048 + 2x16384 — instead of
    (16+2)x16384. (A fully paged KV cache is the next refinement; the
    routing layer is where its block allocator would slot in.)

    Exposes the same surface the server and the multihost lockstep
    driver use (submit/step/idle/metrics), and the routing is a pure
    function of the submission order — multi-host lockstep safe.
    """

    def __init__(self, engines: 'List[InferenceEngine]') -> None:
        if not engines:
            raise ValueError('empty engine pool')
        self.engines = sorted(engines,
                              key=lambda e: e.ecfg.max_seq_len)
        # Disjoint request-id spaces per tier (tier i counts
        # i+1, i+1+n, ...): merged flight-recorder snapshots, the
        # span-store dumps, and `sky-tpu profile <request_id>` all
        # key per-request timelines by request_id — two tiers each
        # counting 1, 2, 3, ... would fold DIFFERENT requests into
        # one timeline. Deterministic in submission order, so
        # multi-host lockstep still agrees on every id.
        for i, eng in enumerate(self.engines):
            eng._ids = itertools.count(i + 1, len(self.engines))
        # One thread steps every tier, so the tiers share one clock: a
        # wait for work goes to the record of whichever tier works
        # next, and the launch after it is the first on any tier.
        clock = self.engines[0]._sl_clock
        for eng in self.engines[1:]:
            eng._sl_clock, eng._stage = clock, clock.stage

    def submit(self, prompt_tokens: Sequence[int],
               max_new_tokens: Optional[int] = None,
               temperature: float = 0.0,
               resume_tokens: Optional[Sequence[int]] = None,
               deadline: Optional[float] = None,
               tenant: str = sched_lib.DEFAULT_TENANT,
               spec: bool = True,
               recv_t: Optional[float] = None,
               lb_recv_t: Optional[float] = None) -> Request:
        n = len(prompt_tokens) + len(resume_tokens or ())
        for eng in self.engines:
            if n <= eng.ecfg.max_seq_len - 1:
                return eng.submit(prompt_tokens, max_new_tokens,
                                  temperature,
                                  resume_tokens=resume_tokens,
                                  deadline=deadline, tenant=tenant,
                                  spec=spec, recv_t=recv_t,
                                  lb_recv_t=lb_recv_t)
        raise ValueError(
            f'prompt ({n} tokens) exceeds every pool tier '
            f'(largest: {self.engines[-1].ecfg.max_seq_len - 1})')

    def cancel(self, req: Request) -> bool:
        for e in self.engines:
            if e.cancel(req):
                return True
        return False

    def step(self) -> int:
        return sum(e.step() for e in self.engines)

    def wait_stage(self) -> Any:
        """The loop's wait for work, on the clock the tiers share."""
        return self.engines[0].wait_stage()

    # -- fleet KV transfers: one advertised index per replica, so the
    # pool delegates to its first prefix-enabled tier (mixed pools are
    # a transitional config; the paged cache subsumes tiering).
    def _kv_engine(self) -> 'InferenceEngine':
        for e in self.engines:
            if e.prefix is not None:
                return e
        raise ValueError('no engine in the pool has a prefix cache')

    def kv_index_armed(self) -> bool:
        return any(e.prefix is not None for e in self.engines)

    def kv_page_size(self) -> int:
        return (self._kv_engine().kv_page_size()
                if self.kv_index_armed() else 0)

    def kv_index_snapshot(self, since_gen: int = -1):
        if not self.kv_index_armed():
            return None
        return self._kv_engine().kv_index_snapshot(since_gen)

    def request_kv_export(self, tokens: Sequence[int]) -> _KVJob:
        return self._kv_engine().request_kv_export(tokens)

    def request_kv_import(self, blob: bytes,
                          fetch_s: float = 0.0) -> _KVJob:
        return self._kv_engine().request_kv_import(blob,
                                                   fetch_s=fetch_s)

    def note_kv_transfer_failure(self) -> None:
        self._kv_engine().note_kv_transfer_failure()

    def kv_transfer_window(self) -> 'List[float]':
        return sorted(x for e in self.engines
                      for x in e.kv_transfer_window())

    def set_pipeline_depth(self, depth: int) -> None:
        for e in self.engines:
            e.set_pipeline_depth(depth)

    def set_wallclock_cancel(self, enabled: bool) -> None:
        for e in self.engines:
            e.set_wallclock_cancel(enabled)

    def set_spec_k(self, k: int) -> None:
        for e in self.engines:
            e.set_spec_k(k)

    def pin_spec_off(self) -> None:
        for e in self.engines:
            e.pin_spec_off()

    def set_scheduler(self, name: str, tenant_weights=None) -> None:
        for e in self.engines:
            e.set_scheduler(name, tenant_weights)

    def set_tenant_weights(self, weights) -> None:
        for e in self.engines:
            e.set_tenant_weights(weights)

    def note_lifecycle_event(self, event: str,
                             t: Optional[float] = None,
                             **detail: Any) -> None:
        """Lifecycle milestones land on tier 0 (the merged snapshot
        interleaves them with every tier's requests anyway)."""
        self.engines[0].note_lifecycle_event(event, t, **detail)

    def note_request_event(self, req: Request, event: str) -> None:
        # Tier i hands out the ids i+1, i+1+n, ... (see __init__).
        self.engines[(req.request_id - 1) % len(self.engines)
                     ].note_request_event(req, event)

    def stepline_snapshot(self) -> Dict[str, Any]:
        """Merged flight-recorder snapshot across tiers (records
        interleave on the shared wall clock)."""
        tiers = [e.stepline_snapshot() for e in self.engines]
        return {
            'enabled': True,
            'dumps': sum(t.get('dumps', 0) for t in tiers),
            'steps_total': sum(t.get('steps_total', 0)
                               for t in tiers),
            'steps': sorted((r for t in tiers
                             for r in t.get('steps', [])),
                            key=lambda r: r['t']),
            'events': sorted((ev for t in tiers
                              for ev in t.get('events', [])),
                             key=lambda ev: ev['t']),
            'tiers': len(tiers),
        }

    def stepline_summary(self) -> Dict[str, Any]:
        tiers = [e.stepline_summary() for e in self.engines]
        if len(tiers) == 1:
            return tiers[0]
        return {'enabled': True, 'tiers': tiers}

    def integrity_suspect(self) -> bool:
        return any(e.integrity_suspect() for e in self.engines)

    def output_digest(self) -> int:
        return zlib.crc32(','.join(
            str(e.output_digest()) for e in self.engines).encode())

    def idle(self) -> bool:
        return all(e.idle() for e in self.engines)

    def compiled_counts(self) -> Dict[str, int]:
        """Per-program compile counts summed over the tiers."""
        total: collections.Counter = collections.Counter()
        for e in self.engines:
            total.update(e.compiled_counts())
        return dict(total)

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        for _ in range(max_steps):
            if self.idle():
                return
            self.step()

    def generate(self, prompts, max_new_tokens=None,
                 temperature: float = 0.0) -> 'List[Request]':
        reqs = [self.submit(p, max_new_tokens, temperature)
                for p in prompts]
        self.run_until_idle()
        return reqs

    def metrics(self) -> Dict[str, Any]:
        tiers = [e.metrics() for e in self.engines]
        # Tiers interleave on the same chip: the honest combined rate
        # is total tokens over total decode time, NOT the sum of
        # per-tier rates (which double-counts wall clock); the pool
        # p50 merges every tier's TTFT window.
        total_time = sum(e._decode_time for e in self.engines)
        total_tokens = sum(t['decode_tokens'] for t in tiers)
        # Per-engine snapshots under each engine's lock — iterating
        # the live _ttfts deques here raced the consume threads'
        # appends (CPython raises on a deque mutated mid-iteration).
        ttfts = sorted(x for e in self.engines
                       for x in e.ttft_window())
        prefixed = [e.prefix for e in self.engines
                    if e.prefix is not None]
        prefix_agg = {}
        if prefixed:
            hits = sum(p.hits for p in prefixed)
            total = hits + sum(p.misses for p in prefixed)
            prefix_agg = {
                'prefix_hit_rate': round(hits / total, 4) if total
                else 0.0,
                'prefix_tokens_saved': sum(p.tokens_saved
                                           for p in prefixed),
                'prefix_cached_pages': sum(p.cached_pages
                                           for p in prefixed),
                'prefix_evictions': sum(p.evictions for p in prefixed),
                'prefix_hits': hits,
                'prefix_misses': total - hits,
                'prefix_indexed_pages': sum(p.indexed_pages
                                            for p in prefixed),
            }
        waits = sorted(x for e in self.engines
                       for x in e.queue_wait_window())
        total_steps = sum(t['decode_steps'] for t in tiers)
        spec_tiers = [t for t in tiers if 'spec_steps' in t]
        spec_agg = {}
        if spec_tiers:
            drafted = sum(t['spec_drafted_tokens'] for t in spec_tiers)
            accepted = sum(t['spec_accepted_tokens']
                           for t in spec_tiers)
            emitted = sum(t['spec_emitted_tokens'] for t in spec_tiers)
            lanes = sum(t['spec_slot_steps'] for t in spec_tiers)
            spec_agg = {
                'spec_k': max(t['spec_k'] for t in spec_tiers),
                'spec_steps': sum(t['spec_steps']
                                  for t in spec_tiers),
                'spec_slot_steps': lanes,
                'spec_drafted_tokens': drafted,
                'spec_accepted_tokens': accepted,
                'spec_emitted_tokens': emitted,
                'spec_accept_rate': (round(accepted / drafted, 4)
                                     if drafted else 0.0),
                'accepted_len_mean': (round(emitted / lanes, 4)
                                      if lanes else None),
            }
        total_prefill = sum(t['prefill_tokens'] for t in tiers)
        kvw = self.kv_transfer_window()
        return {
            **prefix_agg,
            **spec_agg,
            'kv_transfers_total': sum(t['kv_transfers_total']
                                      for t in tiers),
            'kv_transfer_bytes': sum(t['kv_transfer_bytes']
                                     for t in tiers),
            'kv_transfer_failures': sum(t['kv_transfer_failures']
                                        for t in tiers),
            'kv_transfer_p99_s': (round(
                kvw[min(len(kvw) - 1, int(len(kvw) * 0.99))], 6)
                if kvw else None),
            'decode_steps': total_steps,
            'decode_tokens': total_tokens,
            'decode_tokens_per_sec': (total_tokens / total_time
                                      if total_time else 0.0),
            'tokens_per_step': (round(total_tokens / total_steps, 4)
                                if total_steps else None),
            'prefill_tokens': total_prefill,
            'prefill_tokens_per_step': (round(
                total_prefill / total_steps, 4)
                if total_steps else None),
            'fused_steps': sum(t['fused_steps'] for t in tiers),
            'decode_stall_steps': sum(t['decode_stall_steps']
                                      for t in tiers),
            'ttft_p50_s': (ttfts[len(ttfts) // 2] if ttfts else None),
            'queue_wait_p50_ms': (round(
                waits[len(waits) // 2] * 1e3, 3) if waits else None),
            'queue_wait_p99_ms': (round(
                waits[min(len(waits) - 1,
                          int(len(waits) * 0.99))] * 1e3, 3)
                if waits else None),
            'scheduler': tiers[0]['scheduler'],
            'num_waiting': sum(t['num_waiting'] for t in tiers),
            'queued_tokens': sum(t['queued_tokens'] for t in tiers),
            # Exact cross-tier merge from locked raw snapshots (never
            # percentile-of-percentiles).
            'tenants': sched_lib.aggregate_stats(
                [e.sched_snapshot() for e in self.engines],
                total_time),
            'num_active': sum(t['num_active'] for t in tiers),
            'requests_abandoned': sum(t['requests_abandoned']
                                      for t in tiers),
            'requests_expired': sum(t['requests_expired'] for t in tiers),
            'requests_cancelled': sum(t['requests_cancelled']
                                      for t in tiers),
            'pipeline_depth': max(t['pipeline_depth'] for t in tiers),
            'tokens_in_flight': sum(t['tokens_in_flight']
                                    for t in tiers),
            # Flight recorder, summed across tiers — the cataloged
            # top-level keys must survive the two-tier config, or a
            # dashboard keyed on them flatlines when --long-slots is
            # enabled.
            'stepline_steps': sum(t.get('stepline_steps', 0)
                                  for t in tiers),
            'stepline_dumps': sum(t.get('stepline_dumps', 0)
                                  for t in tiers),
            # One clock, so one wait (every tier reads the same).
            'engine_wait_s': tiers[0]['engine_wait_s'],
            **{k: sum(t[k] for t in tiers)
               for k in ('launches', 'launches_device_empty',
                         'launches_after_wait', 'first_token_total',
                         'first_token_early_total')},
            # Integrity: one suspect tier poisons the whole pool (the
            # tiers share a chip — corruption is a device property).
            'sdc_events_total': sum(t.get('sdc_events_total', 0)
                                    for t in tiers),
            'integrity': ('suspect' if any(
                t.get('integrity') == 'suspect' for t in tiers)
                else 'ok'),
            'tiers': [{'max_seq_len': e.ecfg.max_seq_len,
                       'n_slots': e.ecfg.n_slots, **t}
                      for e, t in zip(self.engines, tiers)],
        }
