"""Continuous-batching serving scheduler with a fault-tolerant lifecycle.

Fixed B slots; requests stream in, each slot at its own cache position
(the per-slot `index` vector threaded through Attention.decode).  When a
slot finishes, it is evicted and the next queued request is admitted; its
prompt rides the same steps as the other slots' answers (token-level
interleaving, vLLM-style scheduling at batch granularity).

One step path.  Each step admits, then feeds every live slot a window of
S = speculate + 1 rows through ONE device launch, and keeps what the argmax
of each row accepts (`ContinuousBatcher._window`).  A window's first rows
carry committed tokens: the slot's next prompt tokens, or its last output.
S = 1 is the decode step, through `_step`.  With S > 1, drafter proposals
ride behind the committed rows and `_verify` scores them in the same
launch; its row 0 reproduces the decode step bitwise.  Which of the two
programs runs is fixed by ``speculate`` at construction.

Two cache backends:

  - dense (default): the (slots, max_len) rectangle; every step streams the
    full padded cache and every eviction zeroes max_len rows.  The step is
    `model.decode_step`, given no page table or lengths.
  - paged (``paged=True``): a flat page pool + per-slot page tables
    (runtime/kv_pages).  Admission reserves ceil(expected_tokens/page_size)
    pages from the free list (back-pressuring the queue when the pool is
    exhausted instead of crashing), eviction returns them with NO zeroing,
    and each step attends only over pages the live sequences actually
    touch.  The step is `model.decode_step_paged` (kernels/mx_flash_decode
    under the pallas_mx policy), or `model.verify_step_paged`
    (mx_flash_verify) when speculating.

Two paged admission accelerators (the cross-request reuse PR):

  - ``prefix_cache=True``: a content index over the page pool
    (runtime/prefix_cache) maps each request's longest already-prefilled
    prefix onto resident pages; admission mounts the matched span as
    SHARED (refcounted) pages, COWs at an intra-page divergence, and only
    reserves + prefills the tail.
  - ``prefill_chunk=N``: admission pushes the unmatched tail through
    `model.prefill_step_paged` N tokens per launch, writing K/V directly
    into the slot's pages.  The prompt's LAST token always rides the
    step's window, so the first generated token's launch is identical
    across all admission paths.

The fault-tolerant lifecycle (runtime/lifecycle) on top of both:

  - every request terminates with a typed ``finish_reason`` — including
    over-long prompts ("truncated") and requests still live or queued when
    `run_to_completion` hits max_steps ("deadline", or
    "preempted_requeued" for a preempted request that never got back in) —
    instead of the old bare ``done`` flag and silently-absent entries;
  - priorities + step-denominated TTFT/total deadlines with admission
    load-shedding (a request whose remaining budget cannot cover even its
    optimistic remaining work is shed with "deadline" instead of wasting
    prefill on it) and per-step expiry during prefill and decode;
  - **preemption with page-backed recompute**: under pool exhaustion a
    strictly-lower-priority slot is preempted — its FULL pages (prompt
    *and already-generated tokens*) are published into the `PrefixIndex`
    before release, so re-admission remounts them as shared pages and
    recomputes only the unshared tail (cf. vLLM recompute preemption,
    riding our prefix trie; rollback-free resume is a metadata operation
    thanks to the COW/refcount pool).  Without the prefix index the same
    path degrades to full recompute from the request's token log.
  - chaos injection (`ChaosInjector`) threaded through `step()`:
    transient step failures retry with backoff (the step is functional, a
    retry is a pure recompute), non-finite logits quarantine ONLY the
    poisoned slot ("failed"; other slots' outputs are untouched — greedy
    decode keeps them bitwise identical to a fault-free run), pool
    pressure drives the preemption path, and latency spikes feed the
    `StragglerDetector` watchdog;
  - a per-step `StepHealth` record (`health`, `health_summary()`)
    surfaced by ``serve --paged`` and ``serve --chaos``.

Every step is recorded on the host clock (`time.perf_counter()`, the
clock the profiler stamps host spans with).  Each phase of a step —
admit, prepare, dispatch, wait, emit — is a profiler span
``batcher.<phase>`` inside ``batcher.step`` (with the step's number as
metadata) and a duration in its `StepHealth`; each chunked prefill launch
is a span ``batcher.prefill`` (with ``rid`` and ``tokens``) inside
``batcher.admit``, and the `prefill_launches` / `prefill_tokens` totals
count them.  Every `Request.log_event` carries a wall stamp too.  The
spans cost about a microsecond each and are recorded only while the
profiler runs; the records are always kept.  The jitted decode and
prefill programs are the functions `DECODE_PROGRAM` and `PREFILL_PROGRAM`
name (XLA modules ``jit_step_paged`` and ``jit_prefill_paged``), names
that trace readers key on; tests/test_step_records.py lowers both.  The
prefill, verify and page-copy programs donate the cache; the decode step
does not, since a caller may hand back the cache it passed (the
benchmark's stale-state fault does), at one copy of the pools a step.

CPU-testable end to end with smoke configs (tests/test_batcher.py asserts
outputs are identical to per-request isolated decoding, and pins what the
decode program is fed; tests/test_kv_pages asserts dense/paged parity;
tests/test_prefix_cache asserts dense == paged == prefix-shared;
tests/test_lifecycle.py asserts preempt->resume and under-chaos
exactness)."""

from __future__ import annotations

import time
from collections import Counter, deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..kernels.abft import use_abft
from .fault import DeviceFailure, StragglerDetector
from .kv_pages import PagePool
from .lifecycle import (
    ChaosInjector, FinishReason, Request, RequestState, RetryPolicy,
    StepHealth,
)
from .prefix_cache import PrefixIndex
from .speculative import DraftProposer, NGramDrafter, SpecStats

__all__ = ["ContinuousBatcher", "Request", "FinishReason",
           "DECODE_PROGRAM", "PREFILL_PROGRAM"]

DECODE_PROGRAM = "step_paged"      # name of the jitted paged decode step
PREFILL_PROGRAM = "prefill_paged"  # name of the jitted chunked prefill


class _Phase:
    """One phase of a step: the profiler span ``batcher.<name>`` and its
    host seconds, added to ``StepHealth.<name>_s``."""

    __slots__ = ("health", "field", "span", "t")

    def __init__(self, health: StepHealth, name: str):
        self.health = health
        self.field = name + "_s"
        self.span = TraceAnnotation("batcher." + name)

    def __enter__(self):
        self.span.__enter__()
        self.t = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t
        setattr(self.health, self.field,
                getattr(self.health, self.field) + dt)
        self.span.__exit__(*exc)


class _Slot:
    def __init__(self):
        self.req: Optional[Request] = None
        self.pos = 0           # next cache position to write
        self.prompt_left = 0   # tokens of seq still to feed
        self.seq: Optional[np.ndarray] = None  # prompt + prior output
        self.admit_order = 0   # preemption tie-break: newest victim first
        self.fed = 1           # rows of this step's window fed committed
        self.drafts = ()       # draft tokens riding behind them this step

    @property
    def free(self) -> bool:
        return self.req is None


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


class ContinuousBatcher:
    """model: DecoderLM; params: its params; B slots; max_len cache.

    Each `step` admits, then runs one window of S = speculate + 1 rows per
    live slot through one launch (`_window`); S = 1 is the decode step.

    ``paged=True`` switches to the paged KV cache: ``page_size`` tokens per
    page, ``num_pages`` allocatable pages (default: enough for every slot
    at max_len, i.e. the dense rectangle's capacity — shrink it to see
    admission back-pressure).  ``kv_quant`` (a quantized
    core.precision.QuantSpec, e.g. QuantSpec("int8")) stores the paged
    cache as narrow payloads with per-row scale pages.

    ``prefix_cache=True`` (paged only) shares already-prefilled prompt
    prefixes across requests via the page-granularity content index
    (``prefix_max_pinned`` caps how many pages the index may pin);
    ``prefill_chunk=N`` (paged only) batch-prefills each admitted prompt's
    unmatched tail N tokens per launch directly into its pages.

    ``chaos`` (a lifecycle.ChaosInjector) injects step faults; ``retry``
    controls the transient-failure retry policy; non-finite-logit
    quarantine is on whenever chaos is (it needs a host copy of the
    logits, so the fault-free hot path skips it by default —
    ``nonfinite_guard=True`` forces it on).

    ``speculate=k`` (paged only) widens every step's window to k+1 rows:
    a ``drafter`` (runtime/speculative; default NGramDrafter) proposes up
    to k tokens behind each slot's last committed row, and the verify
    launch (`model.verify_step_paged` -> `mx_flash_verify`) scores them
    with it.  Drafts matching the argmax chain publish (greedy-exact: the
    emitted stream is bitwise-identical to speculate=0); rejected drafts
    roll back by NOT advancing the slot's position/length, their K/V rows
    left stale in the slot's already-reserved pages until real tokens
    overwrite them — zero copies, zero page churn.  Slots still prefilling
    feed up to k+1 prompt tokens as forced rows, so speculation composes
    with chunked prefill, preemption and chaos quarantine unchanged.

    ``abft=True`` arms silent-data-corruption detection end to end: the
    device step traces under `kernels.abft.use_abft()` (every pallas_mx
    GEMM inside it carries checksum verification + in-graph recovery),
    and the host logits copy that token derivation reads is checksummed
    against the device array (identical jnp reduction on both sides, so
    the compare is exact) — on mismatch the copy is re-fetched clean and
    the ``sdc_detected`` / ``sdc_corrected`` counters in
    `health_summary()` advance.  The chaos bitflip stream
    (`ChaosConfig.bitflip_*`) corrupts exactly that host copy, which is
    what the chaos suite drives; with no flip injected the checksums
    agree and the emitted stream is bitwise identical to
    ``abft=False``."""

    def __init__(self, model, params, batch_slots: int, max_len: int,
                 cache_dtype=jnp.float32, *, paged: bool = False,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 kv_quant=None, prefix_cache: bool = False,
                 prefill_chunk: int = 0,
                 prefix_max_pinned: Optional[int] = None,
                 pool: Optional[PagePool] = None,
                 prefix_index: Optional[PrefixIndex] = None,
                 chaos: Optional[ChaosInjector] = None,
                 retry: Optional[RetryPolicy] = None,
                 nonfinite_guard: Optional[bool] = None,
                 speculate: int = 0,
                 drafter: Optional[DraftProposer] = None,
                 abft: bool = False):
        self.model = model
        self.params = params
        self.B = batch_slots
        self.max_len = max_len
        self.paged = paged
        self.slots = [_Slot() for _ in range(batch_slots)]
        self.queue: Deque[Request] = deque()
        self.finished: Dict[int, Request] = {}
        if (prefix_cache or prefill_chunk) and not paged:
            raise ValueError("prefix_cache / prefill_chunk require "
                             "paged=True (they operate on the page pool)")
        if (pool is not None or prefix_index is not None) and not paged:
            raise ValueError("an external pool / prefix_index requires "
                             "paged=True")
        if speculate and not paged:
            raise ValueError("speculate requires paged=True (the verify "
                             "window writes through the page tables)")
        if speculate < 0:
            raise ValueError(f"speculate must be >= 0, got {speculate}")
        if prefix_index is not None:
            if pool is None or prefix_index.pool is not pool:
                raise ValueError("prefix_index must be built over the "
                                 "external pool it is passed with")
        self.prefix: Optional[PrefixIndex] = None
        self.prefill_chunk = int(prefill_chunk)
        self.cow_copies = 0
        self.prefill_launches = 0  # chunked prefill launches issued
        self.prefill_tokens = 0    # prompt tokens those launches carried

        # lifecycle / fault state
        self.chaos = chaos
        self.retry = retry or RetryPolicy()
        self.guard = bool(nonfinite_guard) if nonfinite_guard is not None \
            else chaos is not None
        self.watchdog = StragglerDetector()
        self.steps_run = 0
        self.health: Deque[StepHealth] = deque(maxlen=4096)
        self.preemptions_total = 0
        self.resumes_total = 0
        self.resume_latencies: List[int] = []  # steps preempted -> readmitted
        self.retries_total = 0
        self._submit_order = 0

        # speculative decoding state
        self.speculate = int(speculate)
        self.drafter = (drafter or NGramDrafter()) if self.speculate else None
        self.spec = SpecStats()

        # ABFT (SDC detection) state
        self.abft = bool(abft)
        self.sdc_detected = 0
        self.sdc_corrected = 0

        if paged:
            if not getattr(model, "supports_paged", lambda: False)():
                raise ValueError(
                    "model does not support paged decode (needs attention-"
                    "only segments; state/shared-block archs use dense)")
            # an external pool (disagg: prefill workers and the decode
            # batcher share one allocator, so a handoff is pure metadata)
            # dictates page_size and capacity
            self.page_size = pool.page_size if pool is not None else page_size
            self._table_width = -(-max_len // self.page_size)
            self.pool = pool if pool is not None else PagePool(
                num_pages if num_pages is not None
                else batch_slots * self._table_width,
                self.page_size,
            )
            self.cache = model.make_paged_cache(
                self.pool.total_pages, self.page_size, mode="init",
                dtype=cache_dtype, kv_quant=kv_quant,
            )

            # The prefill, verify and page-copy programs donate the cache
            # (argument 2; 0 for the page copy): they update the pools where
            # they lie, and the caller keeps only what they return.  The
            # decode step does not: its callers may hand back the cache
            # they passed (the benchmark's stale-state fault does), so its
            # input stays valid, at one copy of the pools a step.
            def step_paged(params, token, cache, index, table, lengths):
                return model.decode_step_paged(params, token, cache, index,
                                               table, lengths)

            self._step = jax.jit(step_paged)
            if prefix_index is not None:
                self.prefix = prefix_index
            elif prefix_cache:
                self.prefix = PrefixIndex(self.pool,
                                          max_pinned_pages=prefix_max_pinned)
            if self.prefill_chunk > 0:

                def prefill_paged(params, tokens, cache, index, table):
                    return model.prefill_step_paged(params, tokens, cache,
                                                    index, table)

                self._prefill = jax.jit(prefill_paged, donate_argnums=2)
            if self.speculate > 0:

                def verify_paged(params, tokens, cache, index, table,
                                 lengths):
                    return model.verify_step_paged(params, tokens, cache,
                                                   index, table, lengths)

                self._verify = jax.jit(verify_paged, donate_argnums=2)

            def copy_page(cache, src, dst):
                # paged-cache leaves are layer-stacked (n_layers, P, ...):
                # the page axis is 1.  COW privatization copies one page's
                # rows for every layer and operand (incl. scale sidecars).
                return jax.tree.map(lambda t: t.at[:, dst].set(t[:, src]),
                                    cache)

            self._copy_page = jax.jit(copy_page, donate_argnums=0)
        else:
            if kv_quant is not None:
                raise ValueError("kv_quant requires paged=True (the dense "
                                 "cache dtype is `cache_dtype`)")
            self.pool = None
            self.cache = model.make_cache(batch_slots, max_len, mode="init",
                                          dtype=cache_dtype)

            def step(params, token, cache, index):
                return model.decode_step(params, token, cache, index)

            self._step = jax.jit(step)

    # ------------------------------------------------------------------
    # lifecycle entry points
    # ------------------------------------------------------------------

    def submit(self, req: Request):
        # a pre-set submitted_at survives: the disagg engine stamps arrival
        # before prefill-worker time, so TTFT/deadlines span the WHOLE wait,
        # not just the decode-side queue (engine and batcher share a clock)
        if req.submitted_at < 0:
            req.submitted_at = self.steps_run
        req.state = RequestState.QUEUED
        req.log_event("submitted", self.steps_run)
        req._order = self._submit_order  # FIFO tie-break within a priority
        self._submit_order += 1
        self.queue.append(req)

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or running request ("cancelled"); returns False
        when the rid is unknown or already finished."""
        for req in self.queue:
            if req.rid == rid:
                self.queue.remove(req)
                self._finalize(req, FinishReason.CANCELLED)
                return True
        for i, s in enumerate(self.slots):
            if not s.free and s.req.rid == rid:
                self._finish_slot(i, FinishReason.CANCELLED)
                return True
        return False

    def preempt(self, rid: int) -> bool:
        """Preempt a running request: publish its full pages into the
        prefix index (page-backed resume), release the slot, and requeue it
        with its generated tokens retained.  Returns False when the rid is
        not currently running.  `_admit` calls the same path automatically
        under pool exhaustion when a higher-priority request is waiting."""
        for i, s in enumerate(self.slots):
            if not s.free and s.req.rid == rid:
                self._preempt_slot(i)
                return True
        return False

    # ------------------------------------------------------------------
    # admission / preemption
    # ------------------------------------------------------------------

    HIT_SCAN_LIMIT = 64  # hit-aware admission: queue prefix scanned (FIFO)

    def _pick_next(self) -> Optional[Request]:
        """Highest priority first.  Within the top priority, hit-aware
        ordering: prefer the queued request with the longest resident-
        prefix match (read-only `peek` lookups — only the winner's real
        admission lookup renews LRU recency), so admission consumes fewer
        fresh pages and pool pressure evicts fewer hot pages.  Ties — and
        the whole tier when the index is empty — stay FIFO (a preempted
        request keeps its original submit order, so it re-enters ahead of
        later arrivals of the same priority).  The scan is capped at the
        first HIT_SCAN_LIMIT same-priority requests in FIFO order, keeping
        selection O(limit * prompt pages) however deep the queue."""
        if not self.queue:
            return None
        best = min(self.queue,
                   key=lambda r: (-r.priority, getattr(r, "_order", 0)))
        if self.prefix is None or not self.prefix.entries:
            return best
        cands = sorted((r for r in self.queue if r.priority == best.priority),
                       key=lambda r: getattr(r, "_order", 0))
        cands = cands[:self.HIT_SCAN_LIMIT]
        return max(cands, key=lambda r: (
            self.prefix.lookup(r.sequence(), peek=True).matched_tokens,
            -getattr(r, "_order", 0)))

    def _pick_victim(self, min_priority: int) -> Optional[int]:
        """Preemption victim: the strictly-lower-priority active slot with
        the lowest priority; ties break to the most recently admitted (its
        unshared tail — the only real recompute cost — is shortest)."""
        best, best_key = None, None
        for i, s in enumerate(self.slots):
            if s.free or s.req.priority >= min_priority:
                continue
            key = (s.req.priority, -s.admit_order)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def _estimate_steps(self, req: Request) -> int:
        """Optimistic steps-to-finish if admitted right now, assuming no
        prefix hit (pessimistic on prefill, optimistic on queue wait: the
        remaining budget shrinks every queued step, so a request is shed
        the moment elapsed-wait + this estimate overruns the deadline —
        queue depth times deadline budget, applied incrementally)."""
        prefill = 1 if self.prefill_chunk > 0 else max(len(req.sequence()), 1)
        return prefill - 1 + req.remaining_new()

    def _expire_queued(self, health: StepHealth):
        now = self.steps_run
        for req in list(self.queue):
            waited = now - req.submitted_at
            if ((req.deadline_steps is not None
                 and waited >= req.deadline_steps)
                    or (req.ttft_steps is not None and not req.output
                        and waited >= req.ttft_steps)):
                self.queue.remove(req)
                req.log_event("expired", now)
                self._finalize(req, FinishReason.DEADLINE)
                health.shed.append(req.rid)

    def _shed_hopeless(self, health: StepHealth):
        """Load shedding — only for requests STILL QUEUED after this step's
        admissions: their wait keeps growing, and once elapsed wait plus an
        optimistic steps-to-finish estimate overruns the deadline, burning
        prefill on them would only steal goodput from feasible requests.
        A next-in-line request is never shed here: it gets admitted
        optimistically and the per-step expiry catches it if it does run
        out of budget mid-prefill or mid-decode."""
        now = self.steps_run
        for req in list(self.queue):
            waited = now - req.submitted_at
            if ((req.deadline_steps is not None
                 and waited + self._estimate_steps(req) > req.deadline_steps)
                    or (req.ttft_steps is not None and not req.output
                        and waited + self._estimate_steps(req)
                        - req.remaining_new() + 1 > req.ttft_steps)):
                self.queue.remove(req)
                req.log_event("shed", now)
                self._finalize(req, FinishReason.DEADLINE)
                health.shed.append(req.rid)

    def _expire_running(self, health: StepHealth):
        now = self.steps_run
        for i, s in enumerate(self.slots):
            if s.free:
                continue
            req = s.req
            waited = now - req.submitted_at
            if ((req.deadline_steps is not None
                 and waited >= req.deadline_steps)
                    or (req.ttft_steps is not None and not req.output
                        and waited >= req.ttft_steps)):
                req.log_event("expired", now)
                self._finish_slot(i, FinishReason.DEADLINE)
                health.shed.append(req.rid)

    def _admit(self, health: StepHealth):
        self._expire_queued(health)
        try:
            self._fill_slots(health)
        finally:
            self._shed_hopeless(health)

    def _fill_slots(self, health: StepHealth):
        while self.queue:
            idx = next((i for i, s in enumerate(self.slots) if s.free), None)
            if idx is None:
                return
            req = self._pick_next()
            if self.paged:
                if not self._admit_paged(idx, self.slots[idx], req):
                    # pool exhausted: preempt strictly-lower-priority slots
                    # (publishing their pages for page-backed resume) until
                    # the reservation fits or no victim remains
                    admitted = False
                    while not admitted:
                        victim = self._pick_victim(req.priority)
                        if victim is None:
                            break
                        health.preempted.append(self.slots[victim].req.rid)
                        self._preempt_slot(victim)
                        admitted = self._admit_paged(idx, self.slots[idx],
                                                     req)
                    if not admitted:
                        return  # back-pressure: req stays queued, FIFO kept
                self.queue.remove(req)
            else:
                self.queue.remove(req)
                s = self.slots[idx]
                s.req = req
                s.seq = req.sequence()
                s.pos = 0
                s.prompt_left = len(s.seq)
                self._mark_admitted(s, req)
            health.admitted += 1

    def _mark_admitted(self, s: _Slot, req: Request):
        now = self.steps_run
        s.admit_order = self._submit_order
        self._submit_order += 1
        req.state = RequestState.PREFILL
        if req.preemptions and req.state != RequestState.FINISHED:
            req.log_event("resumed", now)
            self.resumes_total += 1
            for kind, at in reversed(req.events):
                if kind == "preempted":
                    self.resume_latencies.append(now - at)
                    break
        else:
            req.log_event("admitted", now)

    def _admit_paged(self, i: int, s: _Slot, req: Request) -> bool:
        """Paged admission: O(pages touched).  Reserves the request's
        worst-case remaining token footprint up front so decode never
        fails mid-stream; with the prefix cache, the longest
        already-prefilled prefix of the request's token stream (prompt
        plus any generated tokens a preemption left behind) mounts as
        shared pages (plus at most one copy-on-write page at an intra-page
        divergence) and only the tail costs fresh pages + prefill.
        Returns False (nothing changed) when even after index eviction the
        pool cannot cover the fresh pages — the caller back-pressures or
        preempts."""
        seq = req.sequence()
        slen = len(seq)
        tokens = min(self.max_len, slen + req.remaining_new())
        shared: list = []
        partial_page, partial_m = None, 0
        # an over-long prompt (truncation path) skips sharing: its indexed
        # span could exceed the clipped reservation
        if self.prefix is not None and slen + req.remaining_new() <= self.max_len:
            hit = self.prefix.lookup(seq)
            shared = list(hit.pages)
            partial_page, partial_m = hit.partial_page, hit.partial_tokens
        # two plans: with the COW page (costs one extra fresh page for the
        # private copy), then without it
        for use_partial in ((True, False) if partial_m else (False,)):
            plan = shared + ([partial_page] if use_partial else [])
            need_fresh = (self.pool.pages_for(tokens) - len(plan)
                          + (1 if use_partial else 0))
            short = need_fresh - self.pool.pages_free
            if short > 0 and self.prefix is not None:
                # LRU; never frees pinned pages NOR the plan's own hit
                # pages (evicting those would invalidate the reservation
                # we are about to make)
                self.prefix.evict(short, exclude=plan)
            if need_fresh > self.pool.pages_free:
                continue
            if self.pool.try_reserve(i, tokens, shared=plan) is None:
                continue
            if use_partial:
                # privatize the divergent page: guaranteed a free page by
                # the need_fresh accounting above (single-threaded admit)
                src, dst = self.pool.cow(i, len(shared))
                self.cache = self._copy_page(self.cache, src, dst)
                self.cow_copies += 1
            matched = len(shared) * self.page_size + (
                partial_m if use_partial else 0)
            if self.prefix is not None:
                self.prefix.note(matched)
            s.req = req
            s.seq = seq
            s.pos = matched          # next cache position to write
            s.prompt_left = slen - matched
            if matched:
                self.pool.set_length(i, matched)
            self._mark_admitted(s, req)
            if self.prefill_chunk > 0:
                self._prefill_tail(i, s)
            return True
        return False

    def _prefill_tail(self, i: int, s: _Slot):
        """Chunked prefill directly into the slot's pages: positions
        [s.pos, len(seq)-1) go through `prefill_step_paged`, prefill_chunk
        tokens per launch.  The last token is deliberately LEFT to the
        step's window — its launch both writes the final row and produces
        the next-token logits, identically to the token-stepping path
        (and, for a preempted request being resumed, identically to the
        step the preemption interrupted).  An over-long
        prompt (reservation clipped to max_len) prefills only up to the
        last reserved row; the step's window then writes that row and
        trips the same out-of-room truncation the token-stepping path
        degrades through."""
        cap = len(self.pool.owned(i)) * self.page_size
        end = min(len(s.seq) - 1, cap - 1)
        if s.pos >= end:
            return
        table = self.pool.page_table(self.B, self._table_width)[i:i + 1]
        table = jnp.asarray(table)
        while s.pos < end:
            c = min(self.prefill_chunk, end - s.pos)
            with TraceAnnotation("batcher.prefill", rid=s.req.rid, tokens=c):
                toks = jnp.asarray(s.seq[s.pos:s.pos + c][None, :])
                _, self.cache = self._prefill(
                    self.params, toks, self.cache,
                    jnp.asarray([s.pos], np.int32), table,
                )
            s.pos += c
            s.prompt_left -= c
            self.prefill_launches += 1
            self.prefill_tokens += c
            self.pool.set_length(i, s.pos)

    def _preempt_slot(self, i: int):
        """Preemption with page-backed recompute: the slot's FULL pages —
        covering the prompt and every already-generated token whose K/V
        row is resident — are published into the prefix index before
        release, so re-admission mounts them shared and recomputes only
        the unshared tail (the partial last page plus the token the
        interrupted step would have fed).  Without the index the request
        still resumes exactly, via full recompute from its token log."""
        s = self.slots[i]
        req = s.req
        now = self.steps_run
        if self.paged:
            written = s.pos  # rows actually resident (seq[:written])
            if self.prefix is not None and written >= self.page_size:
                self.prefix.insert(s.seq[:written], self.pool.owned(i))
            self.pool.release(i)
        else:
            self._reset_slot_cache(i)
        req.preemptions += 1
        req.state = RequestState.QUEUED
        req.log_event("preempted", now)
        self.preemptions_total += 1
        s.req = None
        s.seq = None
        s.pos = 0
        s.prompt_left = 0
        self.queue.append(req)  # _order is kept: re-enters ahead of peers

    # ------------------------------------------------------------------
    # termination
    # ------------------------------------------------------------------

    def _finalize(self, req: Request, reason: str):
        assert reason in FinishReason.ALL, reason
        req.finish_reason = reason
        req.state = RequestState.FINISHED
        req.finished_at = self.steps_run
        req.log_event(f"finished:{reason}", self.steps_run)
        self.finished[req.rid] = req

    def _finish_slot(self, i: int, reason: str):
        s = self.slots[i]
        self._finalize(s.req, reason)
        s.req = None
        s.seq = None
        if self.paged:
            self.pool.release(i)  # O(1); no zeroing
        else:
            self._reset_slot_cache(i)

    def _reset_slot_cache(self, i: int):
        """Dense backend only: zero slot i's cache rows — an O(max_len)
        write the paged backend replaces with an O(1) free-list release
        (stale page contents are dead via the length mask).  Model caches
        are stacked per segment with the layer dim leading —
        (n_layers, B, ...) — so the slot axis is 1 there; unstacked leaves
        put B first."""
        def zero_row(t):
            if t.ndim >= 2 and t.shape[1] == self.B:
                return t.at[:, i].set(jnp.zeros_like(t[:, i]))
            if t.ndim >= 1 and t.shape[0] == self.B:
                return t.at[i].set(jnp.zeros_like(t[i]))
            return t

        self.cache = jax.tree.map(zero_row, self.cache)

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    @property
    def active(self) -> int:
        return sum(0 if s.free else 1 for s in self.slots)

    def pool_stats(self):
        """Paged backend's allocator stats (None on the dense backend)."""
        return self.pool.stats() if self.pool is not None else None

    def spec_stats(self) -> Optional[dict]:
        """Speculative-decoding acceptance/goodput counters (None when
        speculate=0)."""
        return self.spec.as_dict() if self.speculate else None

    def prefix_stats(self) -> Optional[dict]:
        """Prefix-cache hit/reuse counters (None when prefix_cache off)."""
        if self.prefix is None:
            return None
        st = self.pool.stats()
        out = self.prefix.stats()
        out.update({
            "cow_copies": self.cow_copies,
            "pages_shared": st.pages_shared,
            "pages_reused": st.pages_reused,
            "shared_high_water": st.shared_high_water,
        })
        return out

    def health_summary(self) -> dict:
        """Aggregate watchdog view over the run so far."""
        reasons = Counter(r.finish_reason for r in self.finished.values())
        return {
            "steps": self.steps_run,
            "retries": self.retries_total,
            "preemptions": self.preemptions_total,
            "resumes": self.resumes_total,
            "resume_latency_steps_mean": (
                float(np.mean(self.resume_latencies))
                if self.resume_latencies else 0.0),
            "quarantined": sum(1 for r in self.finished.values()
                               if r.finish_reason == FinishReason.FAILED),
            "shed_or_expired": sum(1 for r in self.finished.values()
                                   if r.finish_reason
                                   == FinishReason.DEADLINE),
            "stragglers": len(self.watchdog.flagged),
            "finish_reasons": dict(reasons),
            "chaos": self.chaos.summary() if self.chaos else None,
            "abft": ({"sdc_detected": self.sdc_detected,
                      "sdc_corrected": self.sdc_corrected}
                     if self.abft else None),
        }

    def _width(self, S: int) -> int:
        """Page-table width for a window of S rows.  It covers the deepest
        live slot's last window row (position pos + S - 1), so a pad row
        past a slot's pages writes to the dump page, never to a clamped
        real page.  Beyond that it is bucketed to the next power of two,
        capped at max_len's pages: the step's gather and grid scale with
        the pages in use, and jit traces O(log) distinct widths."""
        deepest = max(s.pos for s in self.slots if not s.free)
        need = self.pool.pages_for(deepest + S)
        return max(need, min(_next_pow2(need), self._table_width))

    def _room(self, i: int) -> int:
        """Rows slot i may hold: its reserved pages' on the paged backend
        (clipped to min(max_len, prompt + max_new) at admission), max_len
        in the dense rectangle."""
        if self.paged:
            return len(self.pool.owned(i)) * self.page_size
        return self.max_len

    def _attn_pages(self, lengths: np.ndarray) -> int:
        """Pages the step's paged attention reads: each live slot's
        ceil(length / page_size)."""
        return int((-(-lengths // self.page_size)).sum())

    # ------------------------------------------------------------------
    # the step
    # ------------------------------------------------------------------

    def _device_step(self, args, fail_first: bool, fn):
        """One device step under the retry policy.  The injected (or real)
        DeviceFailure is transient: the step function is pure, so a retry
        recomputes from unchanged inputs.  Retries beyond the policy
        re-raise — a permanently failing device is not a serving-loop
        decision.

        The paged verify program donates the cache.  The injected failure
        raises before the call, so the retry's inputs are intact; a real
        failure inside such a call would already have consumed the donated
        pools."""
        attempts = 0
        while True:
            try:
                if fail_first and attempts == 0:
                    raise self.chaos.make_failure(self.steps_run)
                if self.abft:
                    # ambient config is read at TRACE time, so the first
                    # call bakes checksummed GEMMs (with in-graph
                    # recovery) into the jitted executable; reuse is free
                    with use_abft():
                        return fn(*args), attempts
                return fn(*args), attempts
            except DeviceFailure:
                attempts += 1
                self.retries_total += 1
                if attempts > self.retry.max_retries:
                    raise
                if self.retry.backoff_s:
                    time.sleep(self.retry.delay(attempts))

    @staticmethod
    def _logit_checksum(arr) -> np.ndarray:
        """Per-row f32 sum over the vocab axis, computed through the SAME
        jnp reduction whether `arr` lives on device or is a host copy —
        identical data therefore yields bitwise-identical checksums, so
        the compare below is exact (no tolerance, any dtype)."""
        return np.asarray(jnp.sum(jnp.asarray(arr).astype(jnp.float32),
                                  axis=-1))

    def _abft_host_logits(self, device_logits, now: int) -> np.ndarray:
        """Host copy of the logits token derivation will read, verified
        against the device array by exact checksum compare.  The chaos
        bitflip stream corrupts the copy in flight (the host-side SDC
        surrogate); on mismatch the copy is re-fetched clean — recovery
        is a re-transfer, bitwise equal to the fault-free copy."""
        host = np.array(device_logits)
        if self.chaos is not None:
            flip = self.chaos.bitflip(now, host.shape)
            if flip is not None:
                host[flip[0]] += flip[1]
        want = self._logit_checksum(device_logits)
        bad = self._logit_checksum(host) != want
        if bad.any():
            n = int(bad.sum())
            self.sdc_detected += n
            host = np.array(device_logits)
            if (self._logit_checksum(host) == want).all():
                self.sdc_corrected += n
        return host

    def step(self) -> int:
        """One batched step across all slots: admission, then one window
        through the device program; returns #active slots.  Records the
        step's `StepHealth` and its phase spans."""
        now = self.steps_run
        health = StepHealth(step=now, t_start=time.perf_counter())
        launches, prompt_tokens = self.prefill_launches, self.prefill_tokens
        with TraceAnnotation("batcher.step", step=now):
            with _Phase(health, "admit"):
                if self.chaos is not None:
                    self.chaos.begin_step(now, self.pool)
                self._expire_running(health)
                self._admit(health)
            health.prefill_chunks = self.prefill_launches - launches
            health.prefill_tokens = self.prefill_tokens - prompt_tokens
            health.active = self.active
            health.queued = len(self.queue)
            if self.pool is not None:
                health.pages_free = self.pool.pages_free
            ran = self.active > 0
            if ran:
                self._window(health)
            self._flush_health(health, ran_device_step=ran)
        return self.active

    def _window(self, health: StepHealth):
        """The step's device work and emission: every live slot feeds a
        window of S = speculate + 1 rows through one launch, and the host
        keeps what the argmax of each row accepts.  S = 1 is the decode
        step (`_step`); S > 1 is the speculative verify (`_verify`).

        A slot's window starts at its next cache position.  Its first
        ``s.fed`` rows carry committed tokens: the next prompt tokens while
        it prefills (up to S and its reserved room), else its last output.
        Once the window reaches the end of the prompt, the drafter's
        proposals ride behind (`_draft`); the rest are zero pad rows, whose
        writes land past the slot's length in its own reserved pages or on
        the dump page, dead under the length mask.

        Acceptance keeps the fed rows and each draft that matches the
        argmax chain, and publishes them by advancing the slot's position
        and pool length.  A rejected draft's rows are never published: the
        rollback copies nothing, and no page moves (pages are reserved
        worst-case at admission).  Every finish path, the prefix publish,
        the quarantine and the retry policy are the same for every S, so
        the emitted stream is bitwise-identical to speculate=0."""
        now = health.step
        S = self.speculate + 1
        with _Phase(health, "prepare"):
            tokens = np.zeros((self.B, S), np.int32)
            index = np.zeros((self.B,), np.int32)
            lengths = np.zeros((self.B,), np.int32)
            for i, s in enumerate(self.slots):
                if s.free:
                    continue
                s.fed, s.drafts = 1, ()
                if s.prompt_left > 0:
                    if S > 1:  # one row always fits: the plain step skips this
                        s.fed = min(S, s.prompt_left, self._room(i) - s.pos)
                    start = len(s.seq) - s.prompt_left
                    tokens[i, :s.fed] = s.seq[start:start + s.fed]
                else:
                    tokens[i, 0] = s.req.output[-1]
                if s.fed < S and s.fed >= s.prompt_left:  # rows to spare
                    s.drafts = self._draft(i, s, S)
                    tokens[i, s.fed:s.fed + len(s.drafts)] = s.drafts
                index[i] = s.pos
                # the kernel's row-r mask is kpos <= lengths-S+r: pos+S
                # makes row r attend exactly through its own position
                lengths[i] = s.pos + S
            fail = self.chaos.wants_failure(now) if self.chaos else False
            args = (self.params, jnp.asarray(tokens), self.cache,
                    jnp.asarray(index))
            if self.paged:
                health.width = self._width(S)
                health.attn_pages = self._attn_pages(lengths)
                args += (jnp.asarray(self.pool.page_table(self.B,
                                                          health.width)),
                         jnp.asarray(lengths))
        with _Phase(health, "dispatch"):
            # read per call: the benchmark's check wraps `_step` in place
            program = self._verify if self.speculate else self._step
            (logits, self.cache), health.retries = self._device_step(
                args, fail, program)
            if S == 1:
                # (B, vocab): the shape the benchmark warms the eager
                # argmax at, so the window compiles nothing
                logits = logits[:, -1]
            best = None if self.abft else jnp.argmax(logits, axis=-1)
        with _Phase(health, "wait"):
            if self.abft:
                # token derivation reads the VERIFIED host copy (np.argmax
                # and jnp.argmax agree bitwise: both take the first maximal
                # index)
                best = np.argmax(self._abft_host_logits(logits, now), axis=-1)
            rows = np.asarray(best, np.int32).reshape(self.B, S)
            finite = None
            if self.guard:
                host = np.array(logits)  # copy: poisoning writes into it
                if self.chaos is not None:
                    victim = self.chaos.poison_slot(
                        now, [i for i, s in enumerate(self.slots)
                              if not s.free])
                    if victim is not None:
                        host[victim] = np.nan  # the fault the guard catches
                finite = np.isfinite(host).all(axis=-1).reshape(self.B, S)
        with _Phase(health, "emit"):
            for i, s in enumerate(self.slots):
                if s.free:
                    continue
                req = s.req
                fed, drafts = s.fed, s.drafts
                if finite is not None and not (
                        finite[i, :fed + len(drafts)].all()):
                    # quarantine: ONLY this slot fails; its pages are
                    # released and nothing it produced this step is kept
                    # or published
                    health.poisoned.append(req.rid)
                    req.log_event("quarantined", now)
                    self._finish_slot(i, FinishReason.FAILED)
                    continue
                prefilling = s.prompt_left > 0
                completes = fed >= s.prompt_left
                # the last fed row scores the next token
                out = [int(rows[i, fed - 1])] if completes else []
                if drafts:  # each draft the argmax chain agrees with
                    for j, t in enumerate(drafts):
                        if t != out[-1]:
                            break
                        out.append(int(rows[i, fed + j]))
                    agreed = len(out) - 1  # counted before EOS truncation
                    self.spec.windows += 1
                    self.spec.drafted += len(drafts)
                    self.spec.accepted += agreed
                    req.log_event(f"speculated:{agreed}/{len(drafts)}", now)
                hit_eos = req.eos_id in out
                if hit_eos:
                    del out[out.index(req.eos_id) + 1:]
                s.pos += fed
                fed_end = s.pos
                if len(out) > 1:  # the accepted drafts' rows
                    s.pos += len(out) - 1
                if prefilling:
                    s.prompt_left -= fed
                if self.paged:
                    self.pool.set_length(i, s.pos)
                # a slot that exhausted its room (an over-long prompt) is
                # truncated and evicted — capacity exhaustion must degrade,
                # never crash the serving loop
                room = self._room(i)
                out_of_room = s.pos >= room
                if not completes:  # still prefilling: the logits go unused
                    if out_of_room:
                        self._finish_slot(i, FinishReason.TRUNCATED)
                    continue
                if prefilling:
                    # prompt done: this logit starts (or, on resume,
                    # continues) the answer
                    req.state = RequestState.DECODE
                    if self.prefix is not None and fed_end < room:
                        # the sequence's full pages are now immutable
                        # (decode continues in later pages): publish them
                        # for reuse.  Whether it publishes is decided at
                        # the last prompt row, whatever drafts follow.
                        # Pages the slot itself mounted shared dedup
                        # inside the index (existing nodes win, no double
                        # pin).
                        self.prefix.insert(s.seq, self.pool.owned(i))
                req.output.extend(out)
                health.emitted += len(out)
                if req.first_token_at is None:
                    req.first_token_at = now
                    req.log_event("first_token", now)
                self._finish_if_done(i, s, req, hit_eos, out_of_room)
            if self.speculate:
                self.spec.launches += 1
                self.spec.emitted += health.emitted

    def _draft(self, i: int, s: _Slot, S: int) -> tuple:
        """The drafter's proposals to ride behind slot i's last fed row.
        They stay inside the reservation, max_len and the max_new budget:
        the clamp is what makes every finish path land on the token it
        lands on without speculation, and a draft longer than the room left
        degrades to a shorter window instead of corrupting pages."""
        k = min(S - s.fed, min(self._room(i), self.max_len) - s.pos - s.fed,
                s.req.remaining_new() - 1)
        if k <= 0:
            return ()
        prop = np.asarray(self.drafter.propose(s.req.sequence(), k),
                          np.int32).reshape(-1)[:k]
        return tuple(int(t) for t in prop)

    def _finish_if_done(self, i: int, s: _Slot, req: Request, hit_eos: bool,
                        out_of_room: bool):
        """Ends the slot's request when its last emission finished it."""
        if hit_eos:
            self._finish_slot(i, FinishReason.EOS)
        elif len(req.output) >= req.max_new:
            self._finish_slot(i, FinishReason.MAX_NEW)
        elif s.pos >= self.max_len:
            self._finish_slot(i, FinishReason.MAX_LEN)
        elif out_of_room:
            self._finish_slot(i, FinishReason.TRUNCATED)

    def _flush_health(self, health: StepHealth, ran_device_step: bool):
        dt = time.perf_counter() - health.t_start
        if self.chaos is not None:
            dt += self.chaos.latency_spike(health.step)
        health.dt_s = dt
        if ran_device_step:
            health.straggler = self.watchdog.observe(health.step, dt)
        self.health.append(health)
        self.steps_run += 1

    def run_to_completion(self, max_steps: int = 10_000) -> Dict[int, Request]:
        """Drain the queue.  Hitting max_steps is an overload deadline, not
        a silent drop: still-running and still-queued requests terminate
        with "deadline" (a preempted request that never got re-admitted
        with "preempted_requeued"), so every submitted request appears in
        the returned dict with a typed finish_reason."""
        steps = 0
        while (self.queue or self.active) and steps < max_steps:
            self.step()
            steps += 1
        if self.queue or self.active:
            for i, s in enumerate(self.slots):
                if not s.free:
                    self._finish_slot(i, FinishReason.DEADLINE)
            while self.queue:
                req = self.queue.popleft()
                self._finalize(
                    req,
                    FinishReason.PREEMPTED_REQUEUED if req.preemptions
                    else FinishReason.DEADLINE)
        if self.chaos is not None:
            self.chaos.end(self.pool)
        return self.finished
