"""Serving plane: event-driven continuous batching over the SEE++ substrate.

The engine is :class:`ServingEngine` — ``submit(request)`` / ``step()`` /
``drain()`` driven by the :mod:`repro.core.sim` Clock/Executor substrate
(:class:`~repro.core.sim.ThreadExecutor` in production,
:class:`~repro.core.sim.SimExecutor` for seeded deterministic tests).
Every decode slot carries its own live state, so admitting or retiring a
sequence **prefills exactly that sequence** and writes it into its slot —
the O(active·steps) full-batch re-prefill of the old monolithic loop is
gone (``ServerConfig.incremental=False`` keeps the rebatching baseline for
the A/B in ``benchmarks/serve_bench.py``).

Requests carry a tenant: admission routes through the shared
:class:`~repro.core.admission.AdmissionController` slot ledger and
per-tenant :class:`~repro.core.tasks.TenantQuota` slot caps, and the admit
queue is ordered by (priority, deadline, arrival).  Every sequence's KV
pages come from :class:`~repro.core.arena.PagedKVAllocator`; the engine
polls ``kv.validate()`` each step, so a poisoned arena page evicts and
re-prefills its sequence instead of decoding garbage.

With ``ServerConfig.kv_mode="paged"`` (the ``"auto"`` default, for models
that support it) the arena is the *physical* backing store: prefill
scatters K/V rows into the sequence's allocated pages, each decode step
appends one row at ``(page_table[slot, pos // page_size], pos %
page_size)``, and attention runs through the Pallas paged-attention
kernel reading ``kv.page_table()`` directly.  A batch kill then evicts
the *slot*, not the pages — re-admission is a page-table edit (no
re-prefill, no state copy) — while a poisoned sequence still drops its
pages and re-prefills, because they are corrupt by definition.
``kv_mode="dense"`` keeps the per-slot dense reservation for A/B.

With ``ServerConfig.prefill_chunk_tokens > 0`` prefill is *preemptible*:
a freshly admitted slot enters a PREFILLING phase and each ``step()``
advances at most one token-budget's worth of prefill rows across the
prefilling slots before decoding the fully-resident ones — so a single
multi-thousand-token prompt can no longer stall every live stream for
its full prefill.  Paged mode scatters chunk-by-chunk (later chunks
attend through the rows earlier chunks wrote, via the same
``paged_prefill_at`` primitive prefix sharing uses); dense mode threads
a per-slot prefill carry.  Token streams are bit-exact vs monolithic
prefill, and a mid-prefill paged batch kill resumes from the last chunk
boundary.

Token selection is a seeded sampler (:mod:`repro.runtime.sampling`):
temperature / top-k / top-p knobs ride on each :class:`Request` and every
draw is keyed by ``(request.seed, token index)``, so chaos replay — and
evict-and-resume — reproduces token streams byte-for-byte.  Chaos plans
(:class:`~repro.runtime.fault.FailureInjector` ``kill_batch_at_t`` /
``poison_arena_at_t``) land at virtual times under sim, which is what the
seed-swept ``tests/test_serving_chaos.py`` replay suite drives.

:class:`Server` stays the production wrapper: it owns the postprocess
sandbox pool / scheduler / metrics exactly as before and delegates the
serving loop to the engine.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.admission import AdmissionController
from repro.core.arena import PagedKVAllocator
from repro.core.metrics import MetricsHTTPServer, MetricsRegistry
from repro.core.mm import MMConfig
from repro.core.policy import SandboxViolation
from repro.core.pool import SandboxPool
from repro.core.sandbox import Sandbox
from repro.core.sentry import BudgetExceeded
from repro.core.sim import Executor, ThreadExecutor
from repro.core.tasks import ServerlessScheduler, TaskSpec, TaskState, TenantQuota
from repro.core.telemetry import TelemetrySink, resolve_sink
from repro.runtime.sampling import sample_token

__all__ = ["Request", "ServerConfig", "Server", "ServingEngine"]


@dataclass
class Request:
    prompt: np.ndarray                   # (S,) int32
    max_new_tokens: int = 16
    request_id: int = 0
    postprocess: Optional[Callable] = None
    tenant: str = "serving"              # admission identity
    priority: int = 10                   # lower = admitted sooner
    #: seconds after arrival by which the request must be *admitted*;
    #: past it the request completes with an "expired" error instead
    deadline_s: Optional[float] = None
    #: sampling knobs: ``temperature <= 0`` is greedy (argmax); otherwise
    #: top_k > 0 / top_p < 1 truncate the distribution.  ``seed`` keys
    #: the draw together with the token index, so the stream is replay-
    #: deterministic even across evict-and-resume
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    # filled by the engine:
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    latency_s: float = 0.0               # from *arrival*, not server start
    error: Optional[str] = None          # denial/expiry/postprocess failure
    arrived_at: Optional[float] = None   # executor clock, stamped at submit
    admitted_at: Optional[float] = None  # first admission; a chaos-evicted
    # request that was admitted in time is never expired on re-admission


@dataclass
class ServerConfig:
    max_batch: int = 4
    max_seq: int = 256
    tokens_per_page: int = 16
    greedy: bool = True
    mm_legacy: bool = False              # paper A/B: legacy vs modern arena
    pool_watermark: int = 0              # >0: refill postprocess pool async
    workers: int = 0                     # >0: concurrent postprocess plane
    #: >0: reap postprocess workers silent this long mid-task (their task
    #: requeues exactly once, a replacement worker is spawned).  Post-
    #: processors legitimately running longer than this must call
    #: ``repro.core.checkpoint()`` periodically — it heartbeats the
    #: worker (and honors preemption), so live progress is never reaped
    heartbeat_timeout_s: float = 0.0
    #: per-slot incremental prefill (False = the old rebatching baseline:
    #: every admit/retire re-prefills the whole batch; kept for the A/B
    #: in benchmarks/serve_bench.py)
    incremental: bool = True
    #: virtual seconds one decode step occupies on the executor clock;
    #: >0 makes the engine sleep between steps, which is what fires
    #: SimExecutor timers (chaos plans) deterministically under test
    step_time_s: float = 0.0
    #: cap on the engine decision log (0 = unbounded); the default holds
    #: every test/chaos workload in full while bounding always-on servers
    trace_limit: int = 200_000
    #: per-tenant serving quotas: ``max_tasks_in_flight`` caps a tenant's
    #: concurrent decode slots (0 = denied outright); None = no caps.
    #: Tenants absent from a provided dict get the scheduler's default
    #: ``TenantQuota()`` (4 slots), matching the task plane's semantics
    quotas: Optional[Dict[str, TenantQuota]] = None
    #: where the KV cache physically lives.  "paged": the arena's page
    #: pool backs decode and attention runs through the paged-attention
    #: kernel (requires ``incremental`` and a model exposing the paged
    #: interface — see ``models/transformer.py``).  "dense": the per-slot
    #: (B, max_seq) reservation.  "auto": paged when the model supports
    #: it, dense otherwise
    kv_mode: str = "auto"
    #: size of the KV page pool in pages.  None = a generous default
    #: (4x the pages of a full (max_batch, max_seq) reservation, ample
    #: headroom for evicted-but-resident sequences).  Deployments size
    #: this to the expected *live-token* working set instead — that the
    #: pool need not scale with max_seq is the point of paged KV, and
    #: benchmarks/serve_bench.py's sweep sets it accordingly
    kv_pool_pages: Optional[int] = None
    #: cross-tenant prefix sharing (paged mode only): admission consults
    #: the allocator's radix index and maps a matching prompt prefix's
    #: pages read-only (per-page refcounts), prefilling just the suffix;
    #: the first divergent write copy-on-writes the shared page.  Needs
    #: a model exposing paged_prefill_at/paged_copy_page — silently off
    #: otherwise
    prefix_sharing: bool = True
    #: >0: retired requests *park* their sequence (renamed ``~pfxN``)
    #: instead of dropping it, keeping up to this many prefix donors
    #: resident so later requests can share even across idle gaps — the
    #: serving analogue of SEE++'s warm cache.  Parked donors are evicted
    #: FIFO past the cap, dropped on poison, and released by
    #: ``flush_prefix_cache()``.  0 (default) = pages die with the
    #: request, sharing only hits live/resident donors
    prefix_cache_seqs: int = 0
    #: >0: per-step prefill-token budget (chunked prefill).  Admission no
    #: longer prefills its whole prompt synchronously before the decode
    #: batch runs: a freshly admitted slot enters a PREFILLING phase, each
    #: ``step()`` advances at most this many prompt tokens across the
    #: prefilling slots, then decodes the fully-resident slots — so one
    #: multi-thousand-token prompt can no longer stall every live stream
    #: for its full prefill.  A slot joins the decode batch once its
    #: prompt is fully resident; a mid-prefill eviction that keeps pages
    #: (paged batch kill) resumes from the last chunk boundary.  Token
    #: streams are bit-exact vs monolithic prefill.  Requires
    #: ``incremental`` and a model exposing ``paged_prefill_at`` (paged)
    #: or ``prefill_chunk`` (dense).  0 (default) = monolithic prefill
    prefill_chunk_tokens: int = 0


class ServingEngine:
    """Incremental continuous-batching engine on the Clock/Executor substrate.

    ``submit()`` may be called from any thread (and from sim timers);
    ``step()``/``drain()`` run the decode plane.  All bookkeeping is
    guarded by one lock; model math runs outside it.
    """

    def __init__(
        self,
        model,
        params,
        cfg: ServerConfig,
        *,
        executor: Optional[Executor] = None,
        kv: Optional[PagedKVAllocator] = None,
        admission: Optional[AdmissionController] = None,
        telemetry: Optional[TelemetrySink] = None,
        pool: Optional[SandboxPool] = None,
        scheduler: Optional[ServerlessScheduler] = None,
        postprocess_tenant: str = "serving",
        mesh=None,
    ) -> None:
        self.model = model
        self.params = params
        self.cfg = cfg
        self._requested_mesh = mesh
        self._exec = executor or ThreadExecutor()
        self.telemetry = resolve_sink(admission, telemetry)
        self.admission = admission or AdmissionController(sink=self.telemetry)
        self.pool = pool
        self.scheduler = scheduler
        self._post_tenant = postprocess_tenant
        self.kv = kv if kv is not None else self._build_kv(model, cfg)
        self._lock = threading.RLock()
        self._chunked = cfg.prefill_chunk_tokens > 0
        if self._chunked and not cfg.incremental:
            raise ValueError(
                "prefill_chunk_tokens requires incremental=True (the "
                "rebatching baseline re-prefills whole dense batches)"
            )

        B = cfg.max_batch
        self._slots: List[Optional[Request]] = [None] * B
        #: per-tenant admit queues, each ordered by (priority,
        #: deadline-or-inf, arrival seq); the sweep admits the global
        #: minimum across unthrottled tenants, so a capped tenant's
        #: backlog is never heap-churned on the decode hot path
        self._queues: Dict[str, List[Tuple[int, float, int, Request]]] = {}
        #: queued deadline-bearing requests by absolute deadline: expiry
        #: fires on time even for entries buried behind higher-priority
        #: work (heap entries go stale on admission and are skipped)
        self._deadlines: List[Tuple[float, int, Request]] = []
        self._live_ids: set = set()        # queued or slotted request ids
        self._seq = itertools.count()
        #: (task_id, request) pairs awaiting the concurrent postprocess join
        self._post_tasks: Deque[Tuple[int, Request]] = deque()
        #: every completed request; a long-lived server should harvest it
        #: after each drain() and call reset_history() — counters and
        #: gauges survive, only the per-request history is released
        self.completed: List[Request] = []
        #: engine decision log, bounded so an always-on server cannot
        #: grow it without limit (far above any test workload's length)
        self._trace: Deque[str] = deque(maxlen=cfg.trace_limit or None)

        self.kv_mode = self._resolve_kv_mode(model, cfg, mesh)
        self.mesh = mesh if (
            self.kv_mode == "paged" and self._tp_fits(model, mesh)
        ) else None
        self.tp_shards = (
            int(self.mesh.devices.size) if self.mesh is not None else 1
        )
        self.kv.tp_shards = self.tp_shards
        if mesh is not None and self.mesh is None:
            # mesh requested but unusable: dense mode runs replicated,
            # paged mode (explicit, non-dividing model) runs unsharded —
            # record it so tests can pin the graceful-fallback behavior
            self._trace.append(
                f"{self._exec.now():.6f} tp_fallback kv_mode={self.kv_mode}"
            )
        if self.kv_mode == "paged":
            # the arena *is* the backing store: physical page tensors are
            # bound to the allocator and every decode/prefill mutates
            # them in place (donation), addressed by kv's page tables.
            # No dense (B, max_seq) reservation exists in this mode.
            if self.kv.pool_pages is None:
                raise ValueError(
                    "kv_mode='paged' needs a PagedKVAllocator with a "
                    "bounded pool (pool_pages) to size the device pages"
                )
            def init_store():
                return model.init_paged_state(
                    self.kv.pool_pages, self.kv.tokens_per_page
                )

            self._decode_paged = jax.jit(
                model.paged_decode_step, donate_argnums=(1,)
            )
            if self.mesh is None:
                store = init_store()
            else:
                # the mesh's devices hold the params and every physical
                # page, per the model's TP specs (the page *pool* is
                # per-device — each member holds its head/d slice of
                # every page); the pool is built in place, never on
                # another device first.  A one-device mesh is a
                # one-chip replica on that device.  Wider meshes run the
                # decode step under shard_map so the paged-attention
                # kernel grid sees only local heads; the model body
                # psums the logits.  Prefill / scatter / COW stay plain
                # jit: GSPMD reads the same sharded buffers, and
                # exactness is the model's contract (integer ToyLM:
                # bit-exact; transformers: per-head attention is
                # untouched, only the wo psum reorders float adds).
                from jax.sharding import PartitionSpec
                from repro.parallel.sharding import serving_tp_shardings
                pspecs = model.tp_param_specs(self.params)
                poolspecs = model.tp_pool_specs(jax.eval_shape(init_store))
                self.params = jax.device_put(
                    self.params, serving_tp_shardings(self.mesh, pspecs)
                )
                store = jax.jit(
                    init_store,
                    out_shardings=serving_tp_shardings(self.mesh, poolspecs),
                )()
                if self.tp_shards > 1:
                    rep = PartitionSpec()
                    self._decode_paged = jax.jit(
                        jax.shard_map(
                            model.paged_decode_step, mesh=self.mesh,
                            in_specs=(pspecs, poolspecs, rep, rep, rep),
                            out_specs=(poolspecs, rep),
                            check_vma=False,
                        ),
                        donate_argnums=(1,),
                    )
            self.kv.bind_store(store)
            self._state = None
            self._prefill_rows = jax.jit(model.paged_prefill)
            self._scatter_rows = jax.jit(
                model.paged_write_prefill, donate_argnums=(0,)
            )
            self._sharing = (
                cfg.prefix_sharing
                and hasattr(model, "paged_prefill_at")
                and hasattr(model, "paged_copy_page")
            )
            if self._chunked and not hasattr(model, "paged_prefill_at"):
                raise ValueError(
                    "prefill_chunk_tokens (paged) needs a model exposing "
                    "paged_prefill_at — later chunks attend through the "
                    "rows earlier chunks scattered"
                )
            if self._sharing or self._chunked:
                # suffix/chunk prefill reads the pool (resident rows) but
                # does not mutate it — only the scatter/copy donate the
                # store
                self._prefill_rows_at = jax.jit(model.paged_prefill_at)
            if self._sharing:
                self._copy_page = jax.jit(
                    model.paged_copy_page, donate_argnums=(0,)
                )
        else:
            self._sharing = False
            if self._chunked and not hasattr(model, "prefill_chunk"):
                raise ValueError(
                    "prefill_chunk_tokens (dense) needs a model exposing "
                    "prefill_chunk — later chunks continue the carry "
                    "earlier chunks built"
                )
            if self._chunked:
                self._prefill_chunk = jax.jit(model.prefill_chunk)
                # pristine single-slot state: the first chunk's carry.
                # Never donated, so one copy serves every admission
                self._fresh_sub = model.init_decode_state(1, cfg.max_seq)
            # decode state lives per-slot: one persistent batch-state
            # whose slot i is overwritten (incremental mode) on admission
            self._state = model.init_decode_state(B, cfg.max_seq)
            self._decode = jax.jit(model.decode_step, donate_argnums=(1,))
            self._batch_axes = self._find_batch_axes(model, cfg.max_seq)
            self._write_slot = jax.jit(
                lambda state, sub, i: jax.tree_util.tree_map(
                    lambda dst, src, ax: jax.lax.dynamic_update_slice_in_dim(
                        dst, src.astype(dst.dtype), i, ax
                    ),
                    state, sub, self._batch_axes,
                ),
                donate_argnums=(0,),
            )
        # jitted prefill: repeated same-shape admissions are compile-cache
        # hits (the eager path re-traced the whole scan per call); the
        # rebatching baseline still pays a retrace whenever its padded
        # batch shape changes — that churn is part of what it costs
        self._prefill = jax.jit(
            lambda p, toks: model.prefill(p, toks, max_seq=cfg.max_seq)
        )

        # counters (read by MetricsRegistry.register_serving at scrape)
        self._admitted: Dict[str, int] = {}
        self._denied: Dict[str, int] = {}
        self._expired: Dict[str, int] = {}
        self._completed_n: Dict[str, int] = {}
        self._tokens_n: Dict[str, int] = {}
        self._decode_steps = 0
        self._prefills = {"incremental": 0, "full": 0}
        self._prefill_tokens = {"incremental": 0, "full": 0}
        self._prefills_by_request: Dict[int, int] = {}
        self._batch_kills = 0
        self._arena_poisons = 0
        self._evictions = 0
        self._resumes = 0
        self._prefill_chunks = 0
        #: PREFILLING sequences: seq_id -> consumed-stream tokens made
        #: resident so far (the last chunk boundary).  An entry exists
        #: exactly while a sequence's prefill is incomplete — slotted, or
        #: evicted with its pages kept (paged batch kill), where it marks
        #: the point the resumed prefill continues from.  Dropped whenever
        #: the pages drop: no pages, no partial progress
        self._chunk_progress: Dict[str, int] = {}
        #: dense chunked prefill only: seq_id -> the single-slot carry
        #: state accumulated so far.  Held *outside* the batch state until
        #: the final chunk installs it, so intervening decode steps (which
        #: run the whole batch) can never corrupt a half-built slot
        self._chunk_carry: Dict[str, Any] = {}
        #: executor timestamp of each live request's latest sampled token
        #: (keyed by request id) — feeds the inter-token stall histogram
        self._last_tok_t: Dict[int, float] = {}
        self._sampled = {"greedy": 0, "temperature": 0, "topk": 0, "topp": 0}
        self._prefix_hits = 0
        self._prefix_tokens_saved = 0
        #: parked prefix donors (renamed retired sequences), FIFO by
        #: retire order; names may go stale when a poison drops one
        self._parked: Deque[str] = deque()
        self._park_seq = itertools.count()
        #: set by evacuate(): the replica's mesh member is gone — the
        #: engine is inert and a ReplicaSet must not route to it
        self.dead = False

    # ------------------------------------------------------------- helpers

    @staticmethod
    def _tp_fits(model, mesh) -> bool:
        """Whether the model can tensor-parallel over this mesh.

        Needs the TP spec interface *and* exact divisibility (uneven
        head counts must not silently mis-slice under shard_map).
        """
        if mesh is None:
            return False
        n = int(mesh.devices.size)
        return (
            hasattr(model, "tp_supported")
            and hasattr(model, "tp_param_specs")
            and hasattr(model, "tp_pool_specs")
            and bool(model.tp_supported(n))
        )

    @staticmethod
    def _resolve_kv_mode(model, cfg: ServerConfig, mesh=None) -> str:
        supports = bool(getattr(model, "supports_paged_decode", False))
        if cfg.kv_mode == "auto":
            if mesh is not None and supports and cfg.incremental \
                    and not ServingEngine._tp_fits(model, mesh):
                # a mesh was requested but the model's heads don't
                # divide it: fall back to dense (replicated) serving
                # rather than mis-sharding the page pool
                return "dense"
            return "paged" if (supports and cfg.incremental) else "dense"
        if cfg.kv_mode == "paged":
            if not supports:
                raise ValueError(
                    f"kv_mode='paged' but {type(model).__name__} does not "
                    "support paged decode (no paged interface, or it uses "
                    "logit softcap / sliding windows)"
                )
            if not cfg.incremental:
                raise ValueError(
                    "kv_mode='paged' requires incremental=True (the "
                    "rebatching baseline re-prefills dense batches)"
                )
            return "paged"
        if cfg.kv_mode == "dense":
            return "dense"
        raise ValueError(f"unknown kv_mode {cfg.kv_mode!r}")

    @staticmethod
    def _build_kv(model, cfg: ServerConfig) -> PagedKVAllocator:
        mm_cfg = (MMConfig.legacy if cfg.mm_legacy else MMConfig.modern)(
            granule=4096
        )
        mcfg = getattr(model, "cfg", None)
        token_bytes = (
            2 * mcfg.num_kv_heads * mcfg.hd * 2 if mcfg is not None else 1
        )  # K+V bf16
        seq_pages = -(-cfg.max_seq // cfg.tokens_per_page)
        return PagedKVAllocator(
            mm_cfg, tokens_per_page=cfg.tokens_per_page,
            token_bytes=max(token_bytes, 1),
            max_seq_pages=seq_pages,
            pool_pages=cfg.kv_pool_pages or 4 * cfg.max_batch * seq_pages,
        )

    def _find_batch_axes(self, model, max_seq: int):
        """Per-leaf batch axis of the decode state (generic across models).

        The axis whose extent tracks ``batch_size`` in
        ``init_decode_state`` is the one a slot write must slice —
        discovered by diffing abstract shapes at two batch sizes, so any
        model family (dense KV cache, SSM state, RWKV recurrence) works
        without per-family code.
        """
        two = jax.eval_shape(lambda: model.init_decode_state(2, max_seq))
        one = jax.eval_shape(lambda: model.init_decode_state(1, max_seq))

        def axis(a, b):
            for i, (x, y) in enumerate(zip(a.shape, b.shape)):
                if x != y:
                    return i
            raise ValueError(
                f"decode-state leaf has no batch axis: {a.shape}"
            )

        return jax.tree_util.tree_map(axis, two, one)

    def _note(self, event: str, r: Optional[Request], detail: str = "") -> None:
        rid = r.request_id if r is not None else "-"
        tenant = r.tenant if r is not None else "-"
        self._trace.append(
            f"{self._exec.now():.6f} {event} req={rid} tenant={tenant}"
            + (f" {detail}" if detail else "")
        )

    def trace(self) -> List[str]:
        """Engine decisions in order; deterministic under SimExecutor."""
        with self._lock:
            return list(self._trace)

    def trace_text(self) -> str:
        return "\n".join(self.trace()) + "\n"

    def quota(self, tenant: str) -> TenantQuota:
        if self.cfg.quotas is None:
            # no quota config = no caps: every tenant may fill the whole
            # batch (TenantQuota's default of 4 in-flight is a *task*
            # plane default and must not silently cap decode slots)
            return TenantQuota(max_tasks_in_flight=self.cfg.max_batch)
        return self.cfg.quotas.get(tenant, TenantQuota())

    def _seq_id(self, r: Request) -> str:
        return f"req{r.request_id}"

    def _enqueue_locked(self, r: Request) -> None:
        """Push onto the tenant's admit queue: (priority, deadline,
        arrival) order within the tenant; the admit sweep takes the
        global minimum across unthrottled tenants."""
        deadline_at = (
            r.arrived_at + r.deadline_s
            if r.deadline_s is not None else float("inf")
        )
        seq = next(self._seq)
        heapq.heappush(
            self._queues.setdefault(r.tenant, []),
            (r.priority, deadline_at, seq, r),
        )
        if r.deadline_s is not None and r.admitted_at is None:
            heapq.heappush(self._deadlines, (deadline_at, seq, r))

    def _deny_locked(self, r: Request, why: str) -> None:
        r.error = f"admission denied: {why}"
        self._denied[r.tenant] = self._denied.get(r.tenant, 0) + 1
        self._note("deny", r)
        # denials happen before _live_ids.add: this request never owned
        # its id, so releasing it here would strip the guard entry of a
        # LIVE request with the same id (the duplicate-id denial case)
        # and let a later submit crash kv.add_sequence mid-batch
        self._finish_locked(r, release_id=False)
        self.telemetry.emit(
            "serving", "denied", tenant=r.tenant, detail=r.error,
        )

    # -------------------------------------------------------------- submit

    def submit(self, r: Request) -> int:
        """Queue a request for admission; returns its request id.

        Stamps the arrival time (request latency is measured from here).
        Denied on the spot — the request completes immediately with
        ``error`` set — when the tenant's quota allows zero concurrent
        slots, or when the request can never fit: one oversized request
        must fail alone, not crash the shared decode plane mid-batch.
        """
        with self._lock:
            if r.arrived_at is None:
                r.arrived_at = self._exec.now()
            if self.quota(r.tenant).max_tasks_in_flight <= 0:
                self._deny_locked(r, f"tenant {r.tenant!r} has no slots")
                return r.request_id
            if len(r.prompt) == 0:
                self._deny_locked(r, "empty prompt")
                return r.request_id
            if len(r.prompt) + r.max_new_tokens > self.cfg.max_seq:
                self._deny_locked(
                    r,
                    f"prompt+max_new_tokens "
                    f"({len(r.prompt)}+{r.max_new_tokens}) exceeds "
                    f"max_seq={self.cfg.max_seq}",
                )
                return r.request_id
            if r.request_id in self._live_ids:
                # the id names the KV sequence — a collision would crash
                # kv.add_sequence mid-batch and strand the slot
                self._deny_locked(
                    r, f"request_id {r.request_id} is already live"
                )
                return r.request_id
            self._live_ids.add(r.request_id)
            self._enqueue_locked(r)
            self._note("submit", r)
        self._exec.notify()
        return r.request_id

    # --------------------------------------------------------------- admit

    def _active_by_tenant_locked(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for r in self._slots:
            if r is not None:
                out[r.tenant] = out.get(r.tenant, 0) + 1
        return out

    def _expire_due_locked(self, now: float) -> None:
        """Complete-with-error every queued request whose admit deadline
        passed.  Runs off the dedicated deadline heap, so it fires on
        time regardless of batch saturation or queue position.  Entries
        for requests that were admitted in the meantime (a chaos-evicted
        request keeps its satisfied deadline) are stale and skipped;
        their tenant-queue entries are discarded by head cleaning.
        """
        while self._deadlines and self._deadlines[0][0] < now:
            _, _, r = heapq.heappop(self._deadlines)
            if r.done or r.admitted_at is not None:
                continue                   # stale: served or re-queued
            r.error = f"deadline {r.deadline_s}s passed before admission"
            self._expired[r.tenant] = self._expired.get(r.tenant, 0) + 1
            self._note("expire", r)
            self._finish_locked(r)
            self.telemetry.count("serving.expired")

    def _clean_head_locked(
        self, tenant: str
    ) -> Optional[Tuple[int, float, int, Request]]:
        """Skip terminal entries; return the tenant's live head, if any."""
        heap = self._queues.get(tenant)
        while heap:
            _, _, _, r = heap[0]
            if r.done:
                heapq.heappop(heap)        # expired (or defensive discard)
                continue
            return heap[0]
        return None

    def _admit_locked(self) -> List[Tuple[int, Request, bool, int]]:
        """Fill free slots from the queues; returns [(slot, request,
        needs_prefill, shared_prefix_tokens)] admitted.

        Each round admits the globally-best head — (priority, deadline,
        arrival) order — among tenants below their slot cap.  Capped
        tenants' backlogs are left untouched (no heap churn), and their
        heads still expire on deadline.

        In paged mode a batch-killed request's pages survive eviction, so
        its re-admission is a *resume*: the sequence is still resident in
        the arena and needs no prefill — decode continues off the
        existing pages (the eviction-is-a-table-edit property).

        With prefix sharing on, a fresh admission consults the arena's
        radix index first: a prompt whose prefix is already resident
        maps those pages read-only and prefills only the suffix.
        """
        admitted: List[Tuple[int, Request, bool, int]] = []
        active = self._active_by_tenant_locked()
        now = self._exec.now()
        # expire due requests every sweep, even with the batch full — a
        # client must not wait out a saturated batch (or a blocked queue
        # position) to learn its deadline already passed
        self._expire_due_locked(now)
        while None in self._slots:
            best: Optional[Tuple[int, float, int, Request]] = None
            for tenant in sorted(self._queues):
                head = self._clean_head_locked(tenant)
                if head is None:
                    continue
                cap = self.quota(tenant).max_tasks_in_flight
                if active.get(tenant, 0) >= cap:
                    continue               # throttled, not denied
                if best is None or head < best:
                    best = head
            if best is None:
                break
            r = best[3]
            heapq.heappop(self._queues[r.tenant])
            slot = self._slots.index(None)
            self._slots[slot] = r
            if r.admitted_at is None:
                r.admitted_at = now
                # first admission only: a chaos-evicted request's
                # re-admission gap is decode time, not queue wait, and
                # would inflate the histogram during a kill storm
                self.telemetry.observe(
                    "serving.admit_wait_seconds", now - r.arrived_at,
                    tenant=r.tenant,
                )
            active[r.tenant] = active.get(r.tenant, 0) + 1
            seq_id = self._seq_id(r)
            resume = self.kv_mode == "paged" and self.kv.has_sequence(seq_id)
            start = 0
            if resume:
                if seq_id in self._chunk_progress:
                    # the eviction landed mid-prefill and kept the pages:
                    # the chunk pump continues from the last boundary —
                    # nothing already resident is ever re-prefilled
                    pass
                else:
                    # pages survived the eviction: re-entry is a table edit
                    self.kv.ensure_tokens(
                        seq_id, len(r.prompt) + len(r.tokens)
                    )
                self._resumes += 1
            else:
                self.kv.add_sequence(seq_id)
                total = len(r.prompt) + len(r.tokens)
                if self._sharing:
                    donor, match = self.kv.lookup_prefix(r.prompt)
                    # share whole pages *covering* the matched prompt
                    # prefix (a trailing partial page included — the
                    # suffix scatter COWs it), but always prefill at
                    # least one token, and only bother for a full page
                    match = min(match, len(r.prompt), total - 1)
                    if donor is not None and match >= self.kv.tokens_per_page:
                        self.kv.share_prefix(seq_id, donor, match)
                        start = match
                        self._prefix_hits += 1
                        self._prefix_tokens_saved += match
                        self._note(
                            "prefix_share", r,
                            f"donor={donor} tokens={match}"
                        )
                if self._chunked:
                    # PREFILLING phase: pages are allocated chunk-by-chunk
                    # by the pump, so a partial sequence holds exactly the
                    # rows it has scattered — the resume point
                    self._chunk_progress[seq_id] = start
                else:
                    self.kv.append_tokens(seq_id, total - start)
            self.admission.slot_acquired(r.tenant)
            self._admitted[r.tenant] = self._admitted.get(r.tenant, 0) + 1
            self._note("admit", r, f"slot={slot}" + (" resume" if resume else ""))
            admitted.append((slot, r, not resume, start))
        return admitted

    # ------------------------------------------------------------- prefill

    def _sequence_tokens(self, r: Request) -> np.ndarray:
        """The token stream the model has *consumed* for this request.

        Decode feeds ``tokens[-1]`` (or ``prompt[-1]`` on the first
        step), so after k generated tokens the consumed stream is
        ``prompt + [prompt[-1]] + tokens[:k-1]`` — the rebuild a chaos
        eviction prefills must replay exactly that stream, or the
        resumed state (and every later token) silently diverges from an
        uninterrupted run.
        """
        if r.tokens:
            seq = list(r.prompt) + [int(r.prompt[-1])] + r.tokens[:-1]
        else:
            seq = list(r.prompt)
        return np.asarray(seq, np.int32)

    def _prefill_slot(self, slot: int, r: Request, start: int = 0) -> None:
        """Prefill exactly this request and write it into its slot.

        Live slots are untouched: their decode state (and cost already
        paid) survives the admission — the tentpole's perf win.
        Ownership is re-checked under the lock: a watchdog-thread
        ``kill_batch()`` landing between admission and here must not
        burn a prefill (or count one) for an evicted request.  A stale
        write racing the final check only touches a freed slot — a new
        occupant can only be admitted by this (the stepping) thread.
        """
        with self._lock:
            if self._slots[slot] is not r:
                return                     # evicted before the prefill ran
            seq = self._sequence_tokens(r)
        sub, _ = self._prefill(self.params, jnp.asarray(seq[None, :]))
        sub["pos"] = jnp.full_like(sub["pos"], len(seq))
        with self._lock:
            if self._slots[slot] is not r:
                return                     # evicted mid-prefill: discard
            self._prefills["incremental"] += 1
            self._prefill_tokens["incremental"] += int(seq.size)
            self._prefills_by_request[r.request_id] = (
                self._prefills_by_request.get(r.request_id, 0) + 1
            )
            self._note("prefill", r, f"slot={slot} tokens={seq.size}")
        self._state = self._write_slot(
            self._state, sub, jnp.asarray(slot, jnp.int32)
        )

    def _cow_locked(self, seq_id: str, logical: int) -> None:
        """Copy-on-write one logical page if another sequence maps it.

        Remaps the slot onto a fresh page and clones the device rows so
        the other mappers keep reading the original bytes — called
        before *every* write that can land on a shared page (the suffix
        prefill scatter and the decode append).
        """
        if self.kv.page_writable(seq_id, logical):
            return
        src, dst = self.kv.cow_page(seq_id, logical)
        self.kv.swap_store(self._copy_page(
            self.kv.store,
            jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
        ))
        self._note("cow", None, f"seq={seq_id} page {src}->{dst}")

    def _prefill_slot_paged(self, slot: int, r: Request,
                            start: int = 0) -> None:
        """Prefill this request's K/V rows straight into its arena pages.

        The scatter targets come from ``kv.token_positions`` under the
        lock (page allocation happened at admission); the model math runs
        outside it.  Same ownership re-checks as the dense path — a
        chaos eviction mid-prefill discards the work.

        With ``start`` > 0 the first ``start`` positions are shared
        donor pages: only the suffix runs through the model (attending
        through the resident prefix rows), any shared page in the write
        range is COW'd, and the scatter lands on the suffix positions.
        """
        with self._lock:
            if self._slots[slot] is not r:
                return                     # evicted before the prefill ran
            seq = self._sequence_tokens(r)
            seq_id = self._seq_id(r)
            if start:
                # the sequence's own page-table row, bucketed like the
                # decode table so jit compiles O(log max_pages) variants
                table = self.kv.page_table(seq_ids=[seq_id])
                w = max(table.shape[1], 1)
                bucket = 1 << (w - 1).bit_length()
                if bucket > table.shape[1]:
                    table = np.pad(
                        table, ((0, 0), (0, bucket - table.shape[1])),
                        constant_values=-1,
                    )
        if start:
            rows, _ = self._prefill_rows_at(
                self.params, jnp.asarray(seq[None, start:]), self.kv.store,
                jnp.asarray(table), jnp.asarray(start, jnp.int32),
            )
        else:
            rows, _ = self._prefill_rows(
                self.params, jnp.asarray(seq[None, :])
            )
        with self._lock:
            if self._slots[slot] is not r:
                return                     # evicted mid-prefill: discard
            self._prefills["incremental"] += 1
            self._prefill_tokens["incremental"] += int(seq.size - start)
            self._prefills_by_request[r.request_id] = (
                self._prefills_by_request.get(r.request_id, 0) + 1
            )
            self._note(
                "prefill", r,
                f"slot={slot} tokens={seq.size - start}"
                + (f" shared={start}" if start else ""),
            )
            page = self.kv.tokens_per_page
            for lp in range(start // page, -(-seq.size // page)):
                # a divergent write into the trailing shared (partial)
                # page triggers COW before the scatter lands
                self._cow_locked(seq_id, lp)
            page_ids, offsets = self.kv.token_positions(
                seq_id, start, seq.size - start
            )
            self.kv.swap_store(self._scatter_rows(
                self.kv.store, rows,
                jnp.asarray(page_ids), jnp.asarray(offsets),
            ))
            if self._sharing:
                # rows are resident now: this prompt can donate
                self.kv.register_prefix(seq_id, r.prompt)

    # ----------------------------------------------------- chunked prefill

    def _pump_prefill_chunks(self) -> bool:
        """Advance PREFILLING slots by at most one token budget, total.

        The per-step budget (``cfg.prefill_chunk_tokens``) is shared
        across prefilling slots in slot order, so the per-tick prefill
        work is bounded no matter how many long prompts were admitted at
        once — the decode batch that follows runs every tick regardless.
        Returns whether any chunk ran.
        """
        budget = self.cfg.prefill_chunk_tokens
        with self._lock:
            pending = [
                (i, r, self._chunk_progress[self._seq_id(r)])
                for i, r in enumerate(self._slots)
                if r is not None and self._seq_id(r) in self._chunk_progress
            ]
        chunk_fn = (
            self._prefill_chunk_paged if self.kv_mode == "paged"
            else self._prefill_chunk_dense
        )
        worked = False
        for slot, r, p in pending:
            if budget <= 0:
                break
            n = min(budget, len(r.prompt) + len(r.tokens) - p)
            if n <= 0:
                continue
            chunk_fn(slot, r, p, n)
            budget -= n
            worked = True
        return worked

    def _prefill_chunk_paged(self, slot: int, r: Request,
                             p: int, n: int) -> None:
        """One paged chunk: scatter consumed-stream rows [p, p+n) into
        the sequence's arena pages.

        Pages are allocated chunk-by-chunk, so mid-prefill the sequence
        holds exactly its scattered rows.  Chunks after the first (and
        any chunk of a shared-prefix admission) attend through the
        resident rows via ``paged_prefill_at`` — the same primitive
        suffix prefill uses, which is why chunking composes with prefix
        sharing and COW.  Same ownership re-checks as monolithic
        prefill: a chaos eviction mid-chunk discards the work, and the
        progress entry (kept across page-preserving evictions) marks
        where the resumed prefill continues.
        """
        with self._lock:
            if self._slots[slot] is not r:
                return                     # evicted before the chunk ran
            seq = self._sequence_tokens(r)
            seq_id = self._seq_id(r)
            self.kv.ensure_tokens(seq_id, p + n)
            if p:
                # the sequence's own page-table row, bucketed like the
                # decode table so jit compiles O(log max_pages) variants
                table = self.kv.page_table(seq_ids=[seq_id])
                w = max(table.shape[1], 1)
                bucket = 1 << (w - 1).bit_length()
                if bucket > table.shape[1]:
                    table = np.pad(
                        table, ((0, 0), (0, bucket - table.shape[1])),
                        constant_values=-1,
                    )
        if p:
            rows, _ = self._prefill_rows_at(
                self.params, jnp.asarray(seq[None, p:p + n]), self.kv.store,
                jnp.asarray(table), jnp.asarray(p, jnp.int32),
            )
        else:
            rows, _ = self._prefill_rows(
                self.params, jnp.asarray(seq[None, :n])
            )
        with self._lock:
            if self._slots[slot] is not r:
                return                     # evicted mid-chunk: discard
            self._prefill_chunks += 1
            self._prefills["incremental"] += 1
            self._prefill_tokens["incremental"] += n
            self._prefills_by_request[r.request_id] = (
                self._prefills_by_request.get(r.request_id, 0) + 1
            )
            self._note("prefill_chunk", r, f"slot={slot} tokens={n} at={p}")
            page = self.kv.tokens_per_page
            for lp in range(p // page, -(-(p + n) // page)):
                # a write into a shared page (the trailing partial page
                # of a shared prefix) triggers COW before the scatter
                self._cow_locked(seq_id, lp)
            page_ids, offsets = self.kv.token_positions(seq_id, p, n)
            self.kv.swap_store(self._scatter_rows(
                self.kv.store, rows,
                jnp.asarray(page_ids), jnp.asarray(offsets),
            ))
            if p + n >= seq.size:
                # fully resident: leave the PREFILLING phase — the slot
                # joins the decode batch from the next tick
                del self._chunk_progress[seq_id]
                if self._sharing:
                    self.kv.register_prefix(seq_id, r.prompt)
            else:
                self._chunk_progress[seq_id] = p + n

    def _prefill_chunk_dense(self, slot: int, r: Request,
                             p: int, n: int) -> None:
        """One dense chunk: fold consumed-stream rows [p, p+n) into the
        sequence's prefill carry.

        The carry lives *outside* the batch state until the final chunk
        installs it via ``_write_slot`` — intervening decode steps run
        the whole batch (a prefilling slot's lane computes garbage that
        is simply never sampled), so installing early would let them
        corrupt a half-built slot.  ``model.prefill_chunk`` continues
        the carry exactly where the previous chunk stopped, which is
        what makes chunked == monolithic bit-exact.
        """
        with self._lock:
            if self._slots[slot] is not r:
                return                     # evicted before the chunk ran
            seq = self._sequence_tokens(r)
            seq_id = self._seq_id(r)
            carry = self._chunk_carry.get(seq_id, self._fresh_sub)
        carry, _ = self._prefill_chunk(
            self.params, jnp.asarray(seq[None, p:p + n]), carry,
            jnp.asarray(p, jnp.int32),
        )
        with self._lock:
            if self._slots[slot] is not r:
                return                     # evicted mid-chunk: discard
            self.kv.ensure_tokens(seq_id, p + n)
            self._prefill_chunks += 1
            self._prefills["incremental"] += 1
            self._prefill_tokens["incremental"] += n
            self._prefills_by_request[r.request_id] = (
                self._prefills_by_request.get(r.request_id, 0) + 1
            )
            self._note("prefill_chunk", r, f"slot={slot} tokens={n} at={p}")
            if p + n >= seq.size:
                del self._chunk_progress[seq_id]
                self._chunk_carry.pop(seq_id, None)
                done = True
            else:
                self._chunk_progress[seq_id] = p + n
                self._chunk_carry[seq_id] = carry
                done = False
        if done:
            self._state = self._write_slot(
                self._state, carry, jnp.asarray(slot, jnp.int32)
            )

    def _prefill_full(self) -> None:
        """Rebatching baseline: re-prefill every live slot (the old loop)."""
        with self._lock:
            live = [
                (i, r) for i, r in enumerate(self._slots) if r is not None
            ]
            seqs = {i: self._sequence_tokens(r) for i, r in live}
        if not live:
            return
        B = self.cfg.max_batch
        S = max(max(s.size for s in seqs.values()), 1)
        toks = np.zeros((B, S), np.int32)
        for i, _ in live:
            toks[i, : seqs[i].size] = seqs[i][:S]
        state, _ = self._prefill(self.params, jnp.asarray(toks))
        lens = np.zeros((B,), np.int32)
        for i, _ in live:
            lens[i] = seqs[i].size
        state["pos"] = jnp.asarray(lens)
        self._state = state
        with self._lock:
            self._prefills["full"] += 1
            self._prefill_tokens["full"] += int(B * S)
            for i, r in live:
                if self._slots[i] is r:    # skip slots evicted mid-prefill
                    self._prefills_by_request[r.request_id] = (
                        self._prefills_by_request.get(r.request_id, 0) + 1
                    )
            self._note("prefill_full", None, f"live={len(live)} tokens={B*S}")

    # ---------------------------------------------------------------- step

    def step(self) -> int:
        """One engine tick: validate arena, admit, decode once, retire.

        Returns the number of requests retired this tick.  Safe to call
        with nothing active (returns 0 after the admit sweep).
        """
        if self.dead:
            return 0
        self._evict_poisoned()
        with self._lock:
            admitted = self._admit_locked()
        if self._chunked:
            # chunked prefill pumps every tick (not just on admission):
            # a prompt larger than one budget finishes over several steps
            if self._pump_prefill_chunks():
                self.kv.arena.mm.host_vma_count()
        elif admitted:
            if self.cfg.incremental:
                prefill = (
                    self._prefill_slot_paged if self.kv_mode == "paged"
                    else self._prefill_slot
                )
                for slot, r, need, start in admitted:
                    if need:
                        prefill(slot, r, start)
            else:
                self._prefill_full()
            # sample arena occupancy while sequences are live (lazy
            # host-VMA tracking only updates on poll)
            self.kv.arena.mm.host_vma_count()
        paged = self.kv_mode == "paged"
        with self._lock:
            # PREFILLING slots are not live: they join the decode batch
            # only once their prompt is fully resident
            live = [
                (i, r) for i, r in enumerate(self._slots)
                if r is not None and self._seq_id(r) not in self._chunk_progress
            ]
            if live and paged:
                # reserve this step's token row per live slot (idempotent
                # — a mid-step eviction + resume replays the same count),
                # then snapshot the slot-ordered page table.  Its width is
                # bucketed to the next power of two of the widest live
                # sequence, so jit compiles O(log max_pages) variants and
                # the kernel grid tracks *live* tokens, not max_seq.
                pos = np.zeros((self.cfg.max_batch,), np.int32)
                for i, r in live:
                    pos[i] = len(r.prompt) + len(r.tokens)
                    self.kv.ensure_tokens(self._seq_id(r), int(pos[i]) + 1)
                    if self._sharing:
                        # the append lands at pos: COW its page first if
                        # another sequence still maps it
                        self._cow_locked(
                            self._seq_id(r),
                            int(pos[i]) // self.kv.tokens_per_page,
                        )
                # a PREFILLING slot maps to an all--1 table row exactly
                # like an empty one: the decode step's write for that
                # lane scatters out of bounds and is dropped, so partial
                # chunk rows can never be clobbered by decode garbage
                seq_ids = [
                    self._seq_id(r)
                    if r is not None
                    and self._seq_id(r) not in self._chunk_progress
                    else None
                    for r in self._slots
                ]
                table = self.kv.page_table(seq_ids=seq_ids)
                w = max(table.shape[1], 1)
                bucket = 1 << (w - 1).bit_length()
                if bucket > table.shape[1]:
                    table = np.pad(
                        table, ((0, 0), (0, bucket - table.shape[1])),
                        constant_values=-1,
                    )
        if not live:
            return 0

        last = np.zeros((self.cfg.max_batch,), np.int32)
        for i, r in live:
            last[i] = r.tokens[-1] if r.tokens else int(r.prompt[-1])
        if paged:
            store, logits = self._decode_paged(
                self.params, self.kv.store, jnp.asarray(last),
                jnp.asarray(table), jnp.asarray(pos),
            )
            self.kv.swap_store(store)
        else:
            self._state, logits = self._decode(
                self.params, self._state, jnp.asarray(last)
            )
        logits_np = np.asarray(logits)

        retiring: List[Request] = []
        now_t = self._exec.now()
        with self._lock:
            self._decode_steps += 1
            for i, r in live:
                if self._slots[i] is not r:
                    continue               # evicted mid-step by chaos
                tok, method = sample_token(
                    logits_np[i],
                    temperature=r.temperature, top_k=r.top_k,
                    top_p=r.top_p, seed=r.seed, index=len(r.tokens),
                )
                self._sampled[method] += 1
                r.tokens.append(tok)
                if len(r.tokens) == 1:
                    # first sampled token ever for this request (token
                    # streams survive evictions, so this fires once):
                    # time-to-first-token from *arrival* — admit wait,
                    # queueing and the whole prefill are all inside it
                    self.telemetry.observe(
                        "serving.ttft_seconds", now_t - r.arrived_at,
                        tenant=r.tenant,
                    )
                else:
                    prev = self._last_tok_t.get(r.request_id)
                    if prev is not None:
                        # inter-token stall: gaps include any eviction
                        # outage or prefill-induced stall between ticks
                        self.telemetry.observe(
                            "serving.intertoken_seconds", now_t - prev,
                            tenant=r.tenant,
                        )
                self._last_tok_t[r.request_id] = now_t
                if paged:
                    # the row was reserved pre-step; make the count stick
                    self.kv.ensure_tokens(
                        self._seq_id(r), len(r.prompt) + len(r.tokens)
                    )
                else:
                    self.kv.append_tokens(self._seq_id(r), 1)
                self._tokens_n[r.tenant] = self._tokens_n.get(r.tenant, 0) + 1
                if len(r.tokens) >= r.max_new_tokens:
                    # release the KV pages and the slot *before* any user
                    # post-code runs: a failing post-processor can never
                    # leak them, and the slot is immediately reusable
                    r.done = True
                    if not self._park_locked(r):
                        self.kv.drop_sequence(self._seq_id(r))
                    self.admission.slot_released(r.tenant)
                    self._slots[i] = None
                    self._last_tok_t.pop(r.request_id, None)
                    self._note("retire", r, f"slot={i}")
                    retiring.append(r)
        for r in retiring:
            # postprocess outside the engine lock: user code must never
            # gate submit(), metrics scrapes or the chaos watchdogs
            self._postprocess(r)
            with self._lock:
                self._finish_locked(r)
        if retiring:
            self._exec.notify()
        return len(retiring)

    def _park_locked(self, r: Request) -> bool:
        """Park a retiring request's sequence as a prefix-cache donor.

        Instead of dropping its pages, the sequence is renamed to a
        ``~pfxN`` cache entry (``~`` cannot appear in a request-derived
        seq id) so later prompts can share it — the serving analogue of
        SEE++'s warm sandbox cache.  Skipped (returns False → caller
        drops normally) when caching is off, the sequence is poisoned,
        its prompt never got indexed, or another donor already covers
        this prompt (parking a duplicate would just pin pages).
        """
        if not self._sharing or self.cfg.prefix_cache_seqs <= 0:
            return False
        seq_id = self._seq_id(r)
        if seq_id in self.kv.validate() or seq_id not in self.kv.prefix:
            return False
        donor, match = self.kv.lookup_prefix(r.prompt, exclude=(seq_id,))
        if donor is not None and match >= len(r.prompt) - 1:
            return False                   # a sharer can't use more anyway
        name = f"~pfx{next(self._park_seq)}"
        self.kv.rename_sequence(seq_id, name)
        self._parked.append(name)
        self._note("park", r, f"as={name}")
        while len(self._parked) > self.cfg.prefix_cache_seqs:
            old = self._parked.popleft()
            if self.kv.has_sequence(old):  # may be stale after a poison
                self.kv.drop_sequence(old)
        return True

    def flush_prefix_cache(self) -> int:
        """Drop every parked prefix donor; returns how many were freed.

        Live sharers keep the pages they map (the allocator only frees a
        page at refcount zero), so flushing mid-decode is always safe.
        """
        with self._lock:
            n = 0
            while self._parked:
                name = self._parked.popleft()
                if self.kv.has_sequence(name):
                    self.kv.drop_sequence(name)
                    n += 1
            return n

    def _postprocess(self, r: Request) -> None:
        """Dispatch or run the user post-processor for a retired request.

        A sandbox denial marks ``r.error`` (tenant isolation) instead of
        taking down the batch.
        """
        if r.postprocess is None:
            return
        if self.scheduler is not None:
            # concurrent plane: decode never blocks on user code;
            # results are joined in drain()
            self._post_tasks.append((
                self.scheduler.submit(TaskSpec(
                    self._post_tenant,
                    r.postprocess,
                    (jnp.asarray(r.tokens, jnp.int32),),
                    name=f"post-req{r.request_id}",
                )),
                r,
            ))
        else:
            self._postprocess_inline(r)

    def _postprocess_inline(self, r: Request) -> None:
        if self.pool is None:
            try:
                out = r.postprocess(jnp.asarray(r.tokens, jnp.int32))
                r.tokens = [int(t) for t in np.asarray(out)]
            except Exception as e:
                r.error = f"postprocess failed: {e}"
                self.telemetry.emit(
                    "serving", "postprocess_failed", tenant=r.tenant,
                    detail=r.error,
                )
            return
        sb = self.pool.checkout(self._post_tenant)
        discard = False
        try:
            out = sb.run(r.postprocess, jnp.asarray(r.tokens, jnp.int32))
            r.tokens = [int(t) for t in np.asarray(out.value)]
        except Exception as e:
            # the serial plane isolates user post-code exactly like the
            # concurrent plane: the request carries the error, the
            # tainted sandbox is discarded, the engine keeps serving.
            # Sandbox.run re-raises arbitrary user exceptions, so this
            # must catch everything, not just SandboxViolation/Budget
            discard = True
            kind = (
                "denied"
                if isinstance(e, (SandboxViolation, BudgetExceeded))
                else "failed"
            )
            r.error = f"postprocess {kind}: {e}"
            self.telemetry.emit(
                "serving", "postprocess_failed", tenant=r.tenant,
                detail=r.error,
            )
        finally:
            self.pool.checkin(sb, discard=discard)

    def _finish_locked(self, r: Request, *, release_id: bool = True) -> None:
        r.done = True
        if release_id:
            self._live_ids.discard(r.request_id)
        arrived = (
            r.arrived_at if r.arrived_at is not None else self._exec.now()
        )
        r.latency_s = self._exec.now() - arrived
        self._completed_n[r.tenant] = self._completed_n.get(r.tenant, 0) + 1
        self.completed.append(r)
        if r.admitted_at is not None:
            # served-request telemetry only: denials and expiries have
            # their own seepp_serving_* families, and their ~0s samples
            # would flatten the latency histogram during a denial storm
            self.telemetry.count("server.request")
            self.telemetry.observe(
                "server.request_seconds", r.latency_s, tenant=r.tenant,
            )

    # --------------------------------------------------------------- drain

    def has_work(self) -> bool:
        with self._lock:
            return any(self._queues.values()) or any(
                r is not None for r in self._slots
            )

    def drain(self, timeout: float = 300.0) -> List[Request]:
        """Run steps until queue and slots are empty; join postprocessors.

        Under a SimExecutor with ``step_time_s > 0`` each step advances
        the virtual clock, firing scheduled chaos (kills, poison) at
        deterministic times.
        """
        deadline = time.monotonic() + timeout
        while self.has_work():
            self.step()
            if self.cfg.step_time_s > 0:
                self._exec.sleep(self.cfg.step_time_s)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"drain: work remaining after {timeout}s wall time"
                )
        self._join_post_tasks()
        return self.completed

    def _join_post_tasks(self) -> None:
        if not self._post_tasks:
            return
        # join the concurrent postprocess plane: a denied/failed
        # post-processor marks its own request and never takes down
        # the batch (tenant isolation extends to user post-code)
        self.scheduler.drain()
        while self._post_tasks:
            task_id, r = self._post_tasks.popleft()
            rec = self.scheduler.record(task_id)
            if rec.state is TaskState.SUCCEEDED:
                r.tokens = [int(t) for t in np.asarray(rec.result.value)]
            else:
                r.error = f"postprocess {rec.state.value}: {rec.error}"
                self.telemetry.emit(
                    "serving", "postprocess_failed", tenant=r.tenant,
                    detail=r.error,
                )

    # --------------------------------------------------------------- chaos

    def _requeue_locked(self, slot: int, r: Request, why: str,
                        *, drop_pages: bool = True) -> None:
        """Evict a live sequence back to the admit queue (chaos paths).

        Generated tokens survive, so the request resumes where it left
        off — evictions can never lose or double a completion.  With
        ``drop_pages=False`` (paged-mode batch kill) the sequence stays
        resident in the arena and re-admission is a pure page-table edit;
        otherwise the pages are released and re-admission prefills
        prompt+tokens from scratch.
        """
        if drop_pages:
            self.kv.drop_sequence(self._seq_id(r))
            # partial prefill progress dies with the pages: re-admission
            # restarts the chunked prefill from zero
            self._chunk_progress.pop(self._seq_id(r), None)
            self._chunk_carry.pop(self._seq_id(r), None)
        self.admission.slot_released(r.tenant)
        self._slots[slot] = None
        self._evictions += 1
        self._enqueue_locked(r)
        self._note(f"evict:{why}", r, f"slot={slot}")
        self.telemetry.count(f"serving.evict_{why}")

    def kill_batch(self) -> int:
        """Chaos: the decode batch dies mid-flight (node loss under it).

        Every live slot's request is requeued with its tokens intact;
        returns the number of evicted sequences.  Dense mode drops the
        KV pages (the state dies with the batch); paged mode keeps them
        — the pages live in the arena, not the batch, so recovery is a
        page-table edit and the re-admitted sequence decodes on without
        a prefill.
        """
        with self._lock:
            live = [(i, r) for i, r in enumerate(self._slots) if r is not None]
            for i, r in live:
                self._requeue_locked(
                    i, r, "kill", drop_pages=self.kv_mode != "paged"
                )
            self._batch_kills += 1
            self._note("kill_batch", None, f"evicted={len(live)}")
        self.telemetry.count("serving.batch_kill")
        self._exec.notify()
        return len(live)

    def evacuate(self) -> List[Request]:
        """Tear down this replica: return every incomplete request.

        The mesh-member-death path (:class:`~repro.runtime.replica.
        ReplicaSet` reaping a silent replica): live slots evict with
        their tokens intact, queued requests come back untouched, and
        *all* resident sequences — evicted-but-resident pages, parked
        prefix donors — drop, because the pages lived on the dead
        member's shard of the pool.  The returned list is deterministic
        (slot order, then queue (priority, deadline, arrival) order) so
        re-homing them on the survivors replays byte-identically.

        After this the engine is inert: ``step()`` returns 0 and the
        allocator's ledger balances (no page outlives its replica).
        """
        with self._lock:
            out: List[Request] = []
            for i, r in enumerate(self._slots):
                if r is None:
                    continue
                self.kv.drop_sequence(self._seq_id(r))
                self.admission.slot_released(r.tenant)
                self._slots[i] = None
                self._evictions += 1
                self._note("evict:evacuate", r, f"slot={i}")
                out.append(r)
            for tenant in sorted(self._queues):
                heap = self._queues[tenant]
                for _, _, _, r in sorted(heap):
                    if not r.done:
                        out.append(r)
                        self._note("evacuate_queued", r)
                heap.clear()
            self._deadlines.clear()
            self._parked.clear()
            self._chunk_progress.clear()
            self._chunk_carry.clear()
            self._last_tok_t.clear()
            for seq_id in self.kv.sequence_ids():
                # evicted-but-resident sequences and parked donors: the
                # pages died with the mesh member
                if self.kv.has_sequence(seq_id):
                    self.kv.drop_sequence(seq_id)
            self._live_ids.clear()
            self.dead = True
        self._exec.notify()
        return out

    def poison_live(self, index: int = 0) -> Optional[str]:
        """Chaos: poison the ``index``-th live sequence's arena pages.

        Deterministic given the engine state (live ids are sorted).  The
        next :meth:`step` detects it via ``kv.validate()`` and evicts.
        """
        with self._lock:
            live = sorted(
                self._seq_id(r) for r in self._slots if r is not None
            )
            if not live:
                return None
            victim = live[index % len(live)]
            self.kv.poison_sequence(victim)
            self._arena_poisons += 1
            self._trace.append(
                f"{self._exec.now():.6f} poison seq={victim}"
            )
        self.telemetry.count("serving.arena_poison")
        return victim

    def poison_shared(self, index: int = 0) -> Optional[str]:
        """Chaos: poison the ``index``-th sequence whose pages are shared.

        Candidates are live slots plus parked prefix donors (sorted, so
        deterministic given engine state).  Poison propagates to every
        co-mapper of the victim's pages — the whole sharing clique
        evicts and re-prefills, which is exactly the blast radius the
        chaos suite must prove survivable.  Returns None when nothing
        is shared right now.
        """
        with self._lock:
            names = [
                self._seq_id(r) for r in self._slots if r is not None
            ] + [p for p in self._parked if self.kv.has_sequence(p)]
            shared = sorted(
                s for s in names if self.kv.sequence_shared(s)
            )
            if not shared:
                return None
            victim = shared[index % len(shared)]
            self.kv.poison_sequence(victim)
            self._arena_poisons += 1
            self._trace.append(
                f"{self._exec.now():.6f} poison_shared seq={victim}"
            )
        self.telemetry.count("serving.arena_poison")
        return victim

    def poison_prefilling(self, index: int = 0) -> Optional[str]:
        """Chaos: poison the ``index``-th *mid-prefill* sequence's pages.

        Targets chunked prefill specifically: the victim has scattered
        some but not all of its prompt rows.  The next :meth:`step`
        detects it via ``kv.validate()``, evicts the slot and drops the
        partial pages (poisoned rows are corrupt by definition), so
        re-admission restarts the chunked prefill from zero — the
        byte-identical-replay invariant must hold across exactly that
        path.  Returns None when nothing is mid-prefill right now.
        """
        with self._lock:
            prefilling = sorted(self._chunk_progress)
            if not prefilling:
                return None
            victim = prefilling[index % len(prefilling)]
            self.kv.poison_sequence(victim)
            self._arena_poisons += 1
            self._trace.append(
                f"{self._exec.now():.6f} poison_prefilling seq={victim}"
            )
        self.telemetry.count("serving.arena_poison")
        return victim

    def _evict_poisoned(self) -> None:
        # validate under the engine lock: every kv mutation (admit,
        # retire, kill_batch from a watchdog thread) happens under it,
        # so the snapshot can never race a concurrent drop_sequence
        with self._lock:
            bad = self.kv.validate()
            if not bad:
                return
            slotted = {
                self._seq_id(r) for r in self._slots if r is not None
            }
            for i, r in enumerate(self._slots):
                if r is not None and self._seq_id(r) in bad:
                    # poisoned pages are corrupt by definition: always
                    # dropped (even in paged mode), so re-admission
                    # re-prefills from the request's token history
                    self._requeue_locked(i, r, "poison")
            for seq_id in bad:
                if seq_id not in slotted and self.kv.has_sequence(seq_id):
                    # paged mode: an evicted-but-resident sequence (pages
                    # kept across a batch kill) got poisoned while
                    # queued — release the pages now so its re-admission
                    # falls back to a clean prefill instead of resuming
                    # off corrupt rows
                    self.kv.drop_sequence(seq_id)
                    self._chunk_progress.pop(seq_id, None)
                    self._chunk_carry.pop(seq_id, None)
                    self._trace.append(
                        f"{self._exec.now():.6f} drop_resident seq={seq_id}"
                    )
        self._exec.notify()

    # --------------------------------------------------------------- stats

    def active_count(self) -> int:
        with self._lock:
            return sum(1 for r in self._slots if r is not None)

    def _queue_depths_locked(self) -> Dict[str, int]:
        # expired entries linger in the tenant heaps until head cleaning
        # pops them; they are not waiting work and must not be reported
        out: Dict[str, int] = {}
        for tenant, heap in self._queues.items():
            n = sum(1 for (_, _, _, r) in heap if not r.done)
            if n:
                out[tenant] = n
        return out

    def queue_depth(self) -> int:
        with self._lock:
            return sum(self._queue_depths_locked().values())

    def serving_stats(self) -> Dict[str, Any]:
        """Snapshot consumed by ``MetricsRegistry.register_serving``."""
        with self._lock:
            queue = self._queue_depths_locked()
            return {
                "queue_depth": queue,
                "active_slots": self._active_by_tenant_locked(),
                "admitted_total": dict(self._admitted),
                "denied_total": dict(self._denied),
                "expired_total": dict(self._expired),
                "completed_total": dict(self._completed_n),
                "tokens_total": dict(self._tokens_n),
                "decode_steps_total": self._decode_steps,
                "tp_shards": self.tp_shards,
                "prefill_sequences_total": dict(self._prefills),
                "prefill_tokens_total": dict(self._prefill_tokens),
                "batch_kill_total": self._batch_kills,
                "arena_poison_total": self._arena_poisons,
                "evicted_total": self._evictions,
                "kv_mode": self.kv_mode,
                "resumed_total": self._resumes,
                "prefill_chunks_total": self._prefill_chunks,
                "sampled_tokens_total": dict(self._sampled),
                "kv_pages_allocated_total": self.kv.pages_allocated,
                "kv_pages_freed_total": self.kv.pages_freed,
                "prefix_hits_total": self._prefix_hits,
                "prefix_shared_pages_total": self.kv.shared_pages_total,
                "prefix_cow_copies_total": self.kv.cow_copies_total,
                "prefix_prefill_tokens_saved_total": self._prefix_tokens_saved,
            }

    def admit_wait_snapshot(self) -> Tuple[float, float]:
        """(count, sum) of ``serving.admit_wait_seconds`` across tenants.

        The autoscaler differentiates this between ticks to get the mean
        admit wait over its window; the histogram is fed from executor
        timestamps, so the snapshot is deterministic under sim.
        """
        n = 0.0
        s = 0.0
        for (name, _tenant), hist in self.telemetry.histograms().items():
            if name == "serving.admit_wait_seconds":
                n += hist.count
                s += hist.sum
        return (n, s)

    def prefill_counts(self) -> Dict[int, int]:
        """Times each request was prefilled (regression probe for tests)."""
        with self._lock:
            return dict(self._prefills_by_request)

    def reset_history(self) -> None:
        """Release per-request history (long-lived servers, post-harvest).

        Clears ``completed``, the decision trace and the per-request
        prefill counts; aggregate counters and live state are untouched.
        Only call between drains — the lists are the drain's output.
        """
        with self._lock:
            self.completed.clear()
            self._trace.clear()
            self._prefills_by_request.clear()

    def arena_report(self) -> Dict[str, Any]:
        return {
            "total_contiguous_runs": self.kv.total_runs(),
            "host_vmas": self.kv.arena.mm.host_vma_count(),
            "host_vma_high_water": self.kv.arena.mm.host_vma_high_water,
            "mm_stats": self.kv.arena.mm.stats(),
        }


class Server:
    """Production wrapper: pool + scheduler + metrics around the engine."""

    def __init__(self, model, params, cfg: ServerConfig,
                 sandbox: Optional[Sandbox] = None,
                 *,
                 pool: Optional[SandboxPool] = None,
                 admission: Optional[AdmissionController] = None,
                 telemetry: Optional[TelemetrySink] = None,
                 executor: Optional[Executor] = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.telemetry = resolve_sink(admission, telemetry)
        self.admission = admission or AdmissionController(sink=self.telemetry)
        # postprocess sandboxes come from a warm pool; an explicit sandbox
        # (back-compat) is adopted as the pool's first warm entry
        self.pool = pool or SandboxPool(
            admission=self.admission,
            telemetry=self.telemetry,
            refill_watermark=cfg.pool_watermark,
        )
        self.sandbox = sandbox
        if sandbox is not None:
            self._postprocess_tenant = sandbox.tenant
            self.pool.seed(sandbox)
        else:
            self._postprocess_tenant = "serving"
            self.pool.prewarm("serving", 1)
        if cfg.pool_watermark > 0:
            self.pool.set_watermark(self._postprocess_tenant, cfg.pool_watermark)
            self.pool.start_refiller()
        # concurrent postprocess plane: user post-processors dispatch to N
        # scheduler workers instead of running inline on the decode loop
        self.scheduler: Optional[ServerlessScheduler] = None
        if cfg.workers > 0:
            self.scheduler = ServerlessScheduler(
                quotas={
                    self._postprocess_tenant: TenantQuota(
                        max_tasks_in_flight=cfg.workers
                    )
                },
                admission=self.admission,
                pool=self.pool,
                workers=cfg.workers,
            ).start()
            if cfg.heartbeat_timeout_s > 0:
                # node-fault tolerance for user post-code: a worker hung
                # inside a post-processor is reaped, its request's task
                # requeued once, and a fresh worker keeps the plane full
                self.scheduler.enable_heartbeats(
                    cfg.heartbeat_timeout_s, replace_dead=True,
                )
                self.scheduler.start_heartbeat_watchdog(
                    interval_s=max(1e-3, cfg.heartbeat_timeout_s / 4),
                )
        self.engine = ServingEngine(
            model, params, cfg,
            executor=executor,
            admission=self.admission,
            telemetry=self.telemetry,
            pool=self.pool,
            scheduler=self.scheduler,
            postprocess_tenant=self._postprocess_tenant,
        )
        self.metrics = (
            MetricsRegistry()
            .register_sink(self.telemetry)
            .register_admission(self.admission)
            .register_pool(self.pool)
            .register_serving(self.engine)
        )
        if self.scheduler is not None:
            self.metrics.register_scheduler(self.scheduler)
        self._metrics_server: Optional[MetricsHTTPServer] = None
        self.metrics.register_arena(self.kv)   # §IV.A occupancy gauges

    # ------------------------------------------------------------- engine

    @property
    def kv(self) -> PagedKVAllocator:
        return self.engine.kv

    @property
    def completed(self) -> List[Request]:
        return self.engine.completed

    def submit(self, r: Request) -> int:
        return self.engine.submit(r)

    def step(self) -> int:
        return self.engine.step()

    def drain(self, timeout: float = 300.0) -> List[Request]:
        return self.engine.drain(timeout=timeout)

    def run(self, requests: List[Request]) -> List[Request]:
        """Process all requests to completion with continuous batching."""
        for r in requests:
            self.engine.submit(r)
        return self.engine.drain()

    # ------------------------------------------------------------ metrics

    def serve_metrics(self, port: int = 0, host: str = "127.0.0.1") -> MetricsHTTPServer:
        """Expose ``GET /metrics`` (Prometheus text format) over HTTP.

        Idempotent: returns the already-running endpoint if one exists.
        ``port=0`` binds an ephemeral port; read it from ``.port``.
        """
        if self._metrics_server is None:
            self._metrics_server = MetricsHTTPServer(
                self.metrics, port=port, host=host
            )
        return self._metrics_server

    def dump_metrics(self) -> Dict[str, Any]:
        """Snapshot of every exported sample (tests/tooling; no HTTP)."""
        return self.metrics.dump()

    def close(self) -> None:
        """Stop metrics, the postprocess workers and the pool refiller."""
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None
        if self.scheduler is not None:
            self.scheduler.shutdown()
        self.pool.stop_refiller()

    # ------------------------------------------------------------- report

    def admission_report(self) -> Dict[str, Any]:
        return {
            "admission": self.admission.stats(),
            "pool": self.pool.stats.as_dict(),
        }

    def arena_report(self) -> Dict[str, Any]:
        return self.engine.arena_report()
