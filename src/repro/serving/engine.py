"""Serving engines over the ParisKV decode path.

``ServingEngine`` (the default) is a **slot-based continuous-batching
scheduler** (paper Fig. 2 lifecycle; LouisKV/FreeKV-style per-request
state):

* The device holds a fixed pool of ``max_batch`` cache slots
  (``models.serve.SlotState``): stacked per-layer caches plus per-slot
  ``pos`` / ``enc_end`` / ``cur_tok`` / ``remaining`` vectors. Rows are
  fully independent — per-row CacheRegions, per-row sliding-window
  promotion — so slots never run in lockstep.
* Admission happens at any chunk boundary. With the default
  ``prefill_budget=0`` a queued request is prefilled **solo** (batch=1,
  prompt LEFT-aligned and padded to a power-of-two length bucket to
  bound compilations) and its cache rows are scattered into a free slot —
  every decoding slot stalls for that full prompt-length forward pass.
  With ``prefill_budget=P > 0`` admission merely **copies the prompt to a
  device buffer** (one compiled shape for every prompt length) and the
  prompt is prefilled *inside the decode chunk*: each mixed step
  processes P prompt tokens for the (at most one) filling slot plus one
  decode token for every active slot, sharing the batched layer pass —
  Sarathi-style chunked prefill, ending prefill head-of-line blocking.
  The filling slot emits its first token the step its fill completes.
* Decoding runs as a **multi-token inner loop**: ``decode_chunk`` scans
  ``chunk_size`` steps on-device (greedy argmax sampling + per-slot active
  mask), so the host syncs once per chunk instead of once per token.
* ``cancel(uid)`` evicts a request at the next chunk boundary — even
  mid-fill — reclaiming its slot (and, on the paged engine, its blocks
  and histogram rows) immediately.

Timing is honest and per-request: ``ttft_s`` is measured from the moment
the request is admitted (popped from the queue) to its first token being
ready on the host; ``decode_s`` is the wall time from first token to the
end of the chunk in which the request finished (chunk-boundary
granularity, ± chunk_size·TPOT). ``token_times`` records when each output
token became host-visible (chunk granularity) — the decode-stall metric
(max inter-token gap) in ``benchmarks/bench_continuous_batching.py`` is
computed from it. ``t_submit`` / ``t_admit`` stamp when ``submit()`` took
a request and when it left the queue.

Each serving round runs under host spans (``SPAN_*``: admit, pre-chunk,
dispatch, wait, collect; ``jax.profiler.TraceAnnotation``), so a device
trace taken around ``step_serve()`` says what the host was doing in each
of the device's idle gaps; the decode chunk's own ops carry the phase
scopes of ``core.phases``.

``PagedServingEngine`` replaces the per-slot contiguous ``n_max`` regions
with a **paged KV cache**: one global pool of fixed-size token blocks
(``num_blocks × block_size``) shared by all slots, plus per-slot block
tables mapping logical positions to ``(block_id, offset)``. Admission is
gated by *free-block count* (worst-case ``⌈(prompt+gen)/block_size⌉``
reservation, so a request admitted can always finish — honest OOM
backpressure instead of mid-flight deadlock), physical blocks are
allocated lazily at chunk boundaries as each slot's appends approach
them, and eviction reclaims (and zeroes) a slot's blocks for immediate
reuse. Short requests no longer strand ``n_max``-sized regions, so a
fixed pool admits far more concurrent mixed-length requests
(``benchmarks/bench_continuous_batching.py`` measures the ratio). It
takes the same ``prefill_budget`` knob: chunked fills append K/V and
metadata through the block table and keep the slot's incremental bucket
histogram exact at every mixed step.

Paged decoding defaults to the **fused retrieval path** (``fused=True``):
Stage I scores the pool's centroid ids through the block table against
tier weights built from an *incrementally maintained* per-slot bucket
histogram (computed at admission, O(U)-updated at promotion — and O(P)
per chunked-fill step — zeroed at eviction; ``batch × G × B × 2^m``
int32 of extra state per layer), and Stage II gathers only the ≤C
candidates' codes/weights by physical row. On TPU the fused path runs
the Pallas kernels (``collision_paged_pallas``, ``rerank_paged_kernel``)
instead of their jnp twins; ``REPRO_PALLAS_INTERPRET=1`` forces the
twins back. The per-step ``paged_meta_view`` materialization (9·B
bytes/key, every decode step) is gone; ``fused=False`` brings it back —
kept for A/B and bisection; ``benchmarks/bench_kernels.py`` measures the
gap. The two are token-identical whenever ``pariskv.hist_sample == 0``
(the default).

``share_prefixes=True`` (paged engines, requires ``prefill_budget > 0``)
turns on **block-granular prefix sharing with copy-on-write** (ISSUE 7):
full prompt blocks are content-hashed (a chained hash per
``block_size``-token chunk, so a block's identity covers everything
before it), registered in a pool-level ``prefix_index`` when their fill
completes, and mapped — not copied — into later admissions' block tables
with a per-block refcount. ``admit_fill`` then starts the fill frontier
past the shared prefix and chunk-fills only the unshared suffix; the
block holding the last prompt token is never shared, so every write a
slot performs (suffix fill, decode appends) lands in private blocks —
copy-on-write by construction, no fault path needed. Reclamation is
refcounted end to end: eviction/cancel/finish decrement, and a block is
zeroed + returned to the free list (and dropped from the index) only at
refcount 0; backpressure reservation counts only the *unshared* blocks a
request will actually consume. A fleet sharing an 8k system prompt costs
one set of prefix blocks plus one suffix fill per request — near-flat
block cost and TTFT cut by ~the shared fraction, token-identical to the
no-sharing path (tests/test_prefix_sharing.py pins fused/fallback and
resident/offloaded; ``benchmarks/bench_continuous_batching.py``'s
``prefix_sharing`` scenario gates all three claims in CI).

``WaveServingEngine`` preserves the previous lockstep wave scheduler
(padded-batch prefill, whole-wave decode) as a baseline for
``benchmarks/bench_continuous_batching.py``. Its timing is wave-level by
construction and documented as such.

Deferred (ROADMAP · Reach): chunked prefill for SSM/cross mixers
(attention and MLA architectures today), the host tier and the mesh for
MLA latent pools, non-greedy sampling, and cross-run prefix persistence
(the prefix index only retains blocks some live request still holds).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import time
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as CC
from repro.core import retrieval as R
from repro.core.config import ModelConfig
from repro.models import layers as L
from repro.models import serve as SV
from repro.serving import offload as offload_lib
from repro.serving.faults import FaultPlan


class InvariantViolation(AssertionError):
    """``verify_invariants()`` found engine state that breaks a structural
    invariant (allocator accounting, block-table/refcount consistency,
    staging residency, or the incremental-histogram identity). Raised —
    not logged — so tests and the chaos harness fail loudly."""


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (s,) int32
    max_new_tokens: int = 32
    media: Optional[np.ndarray] = None
    # filled by the engine:
    output: Optional[np.ndarray] = None
    ttft_s: float = 0.0             # admission → first token (per request)
    decode_s: float = 0.0           # first token → completion (per request)
    cancelled: bool = False
    token_times: Optional[list] = None   # host-visibility time per token
    # offloaded-engine fetch observability (ISSUE 6; zero elsewhere):
    staging_hits: int = 0        # winner head-rows served from staging
    staging_misses: int = 0      # winner head-rows fetched from the host tier
    fetched_bytes: int = 0       # requested K+V bytes moved host → device
    fetched_unique_bytes: int = 0  # after head/query dedup (ISSUE 9) —
    #                              the bytes the host actually gathered,
    #                              attributed ∝ this request's fetch rows
    prefetched_blocks: int = 0   # blocks speculatively staged for this req
    prefetch_hits: int = 0       # prefetched blocks referenced next chunk
    # fetch-pipeline observability (ISSUE 9; zero elsewhere):
    fetch_stall_s: float = 0.0   # decode-step seconds blocked on host
    #                              fetches, attributed ∝ fetch rows
    fetch_callbacks: int = 0     # host callbacks attributed the same way
    # prefix-sharing observability (ISSUE 7; zero unless share_prefixes):
    shared_prefix_blocks: int = 0  # already-cached blocks mapped, not filled
    # fault-tolerance observability (ISSUE 10; zero elsewhere):
    fetch_retries: int = 0       # host-fetch attempts beyond the first,
    #                              attributed ∝ this request's fetch rows
    fetch_timeouts: int = 0      # fetch deadlines that fired (worker
    #                              abandoned + respawned), same attribution
    degraded_steps: int = 0      # (layer, step) fetches that exhausted
    #                              retries: attention fell back to sink +
    #                              window + resident-staged blocks only
    failed: bool = False         # quarantined by an engine fault
    error: Optional[str] = None  # the quarantining exception, rendered
    # host-clock stamps (time.perf_counter): submit() took the request,
    # and the engine popped it off the queue (None until then)
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    # engine-internal:
    _tokens: Optional[list] = None
    _t_first: float = 0.0


# host spans of one serving round, on the profiler's clock: a device trace
# taken around step_serve() shows which of them the host was in
SPAN_ADMIT = "engine.admit"          # one request's admission (arg: uid)
SPAN_PRE_CHUNK = "engine.pre_chunk"  # block allocation, staging update
SPAN_DISPATCH = "engine.dispatch"    # launching the jitted decode chunk
SPAN_WAIT = "engine.wait"            # blocking on the chunk's tokens
SPAN_COLLECT = "engine.collect"      # fetch stats, tokens, finishes


def _bucket(n: int, floor: int = 8, cap: Optional[int] = None) -> int:
    """Smallest power of two ≥ max(n, floor), clamped to ``cap``.

    The clamp applies *before* the doubling loop: an oversized floor (or
    a cap below the floor) can never make the loop overshoot the cap."""
    if cap is not None and n >= cap:
        return cap
    b = floor if cap is None else min(floor, cap)
    while b < n:
        b *= 2
    return b if cap is None else min(b, cap)


def _solo_prefill(prefill_fn, params, req: Request, n_max: int):
    """Solo (batch=1) prefill of a request's prompt, LEFT-aligned and
    padded to a power-of-two bucket (capped at n_max: submit() already
    guarantees prompt + gen ≤ n_max). Returns (state1, tok0) — shared by
    the contiguous and paged engines."""
    s = _bucket(len(req.prompt), cap=n_max)
    toks = np.zeros((1, s), np.int32)
    toks[0, :len(req.prompt)] = req.prompt
    lens = jnp.asarray([len(req.prompt)], jnp.int32)
    media = None
    if req.media is not None:
        media = jnp.asarray(req.media)[None]
    logits, state1 = prefill_fn(params, jnp.asarray(toks), lens, media)
    tok0 = int(jnp.argmax(logits[0], -1))    # blocks: first token
    return state1, tok0


def _collect_chunk_row(req: Request, row: np.ndarray, t_now: float) -> int:
    """Append a slot's valid chunk emissions to the request.

    -1 marks steps the slot did not emit: inactive/finished steps at the
    *tail* and — under chunked prefill — fill steps at the *head* (the
    first token appears mid-chunk, the step the fill completes). Valid
    emissions are therefore the contiguous non-negative run; with eos_id,
    remaining jumps to 0 so rem_before - rem_after would over-count — the
    sentinel scan is the reliable source. Stamps each collected token
    with ``t_now`` (chunk-boundary granularity) for the stall metric.
    Returns the number of tokens emitted this chunk."""
    nonneg = np.flatnonzero(row >= 0)
    if nonneg.size == 0:
        return 0
    tail = row[nonneg[0]:]
    n_emit = int(np.argmax(tail < 0)) if (tail < 0).any() else len(tail)
    req._tokens.extend(tail[:n_emit].tolist())
    if req.token_times is not None:
        req.token_times.extend([t_now] * n_emit)
    return n_emit


def _finalize_output(req: Request, eos_id: Optional[int],
                     t_now: float) -> None:
    """Fix up a finished request: clip to max_new_tokens, truncate at the
    first eos, set decode wall time."""
    out = np.asarray(req._tokens[:req.max_new_tokens], np.int32)
    if eos_id is not None and eos_id in out:
        out = out[:int(np.argmax(out == eos_id)) + 1]
    req.output = out
    if req.token_times is not None:
        req.token_times = req.token_times[:len(out)]
    req.decode_s = t_now - req._t_first


class ServingEngine:
    """Slot-based continuous-batching engine (see module docstring).

    ``prefill_budget=0`` (default): solo blocking prefill at admission.
    ``prefill_budget=P``: chunked prefill fused into the decode chunk —
    admission only copies the prompt to the device; the scan consumes P
    prompt tokens per mixed step. Token-identical to the solo path
    (tests/test_chunked_prefill.py); attention and MLA architectures
    (``models.serve.fill_supported``).
    """

    def __init__(self, cfg: ModelConfig, params, n_max: int = 4096,
                 max_batch: int = 8, greedy: bool = True, use_pariskv=True,
                 chunk_size: int = 8, eos_id: Optional[int] = None,
                 prefill_budget: int = 0,
                 faults: Optional[FaultPlan] = None):
        assert greedy, "sampling is on-device argmax; greedy only for now"
        if prefill_budget and not SV.fill_supported(cfg):
            raise ValueError(
                f"chunked prefill (prefill_budget={prefill_budget}) "
                f"unavailable — {SV.fill_support_reason(cfg)}; use "
                f"prefill_budget=0")
        self.cfg = cfg
        self.params = params
        self.n_max = n_max
        self.max_batch = max_batch
        self.use_pariskv = use_pariskv
        self.chunk_size = chunk_size
        self.eos_id = eos_id
        self.prefill_budget = prefill_budget
        self.faults = faults
        self.quarantined: List[Request] = []
        self._prefill = jax.jit(
            lambda p, t, lens, m: SV.prefill(p, cfg, t, n_max, m,
                                             lengths=lens))
        self._chunk = jax.jit(
            lambda p, st: SV.decode_chunk(p, cfg, st, chunk_size,
                                          use_pariskv=use_pariskv,
                                          eos_id=eos_id,
                                          prefill_budget=prefill_budget),
            donate_argnums=(1,))
        self._admit_fn = jax.jit(self._admit_impl, donate_argnums=(0,))
        self._admit_fill_fn = jax.jit(SV.admit_fill, donate_argnums=(0,))
        self._cancel_fn = jax.jit(SV.cancel_slot, donate_argnums=(0,))
        self.queue: List[Request] = []
        self.peak_concurrency = 0   # max slots simultaneously decoding
        # serving-loop state (start()/step_serve())
        self._state = None
        self._slots: List[Optional[Request]] = []
        self._done: List[Request] = []
        self._filling: Optional[int] = None   # slot currently chunk-filling
        self._cancelled: set = set()

    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.n_max:
            raise ValueError(
                f"request {req.uid}: prompt {len(req.prompt)} + "
                f"{req.max_new_tokens} new tokens exceeds n_max={self.n_max}")
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def cancel(self, uid: int) -> None:
        """Evict request ``uid`` at the next chunk boundary (queued → drop;
        in-flight or mid-fill → slot/cache reclaimed, partial output)."""
        self._cancelled.add(uid)

    # ------------------------------------------------------ device helpers --
    @staticmethod
    def _admit_impl(state: SV.SlotState, slot, caches1, regions1, tok0, rem):
        """Scatter a batch=1 prefill result into cache slot ``slot``.

        Every cache leaf is stacked (repeat, b, ...) — batch is uniformly
        axis 1, so one dynamic_update_slice per leaf installs the row.
        """
        caches = jax.tree.map(
            lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                big, small, slot, axis=1),
            state.caches, caches1)
        return state._replace(
            caches=caches,
            regions=CC.CacheRegions(
                pos=state.regions.pos.at[slot].set(regions1.pos[0]),
                enc_end=state.regions.enc_end.at[slot].set(
                    regions1.enc_end[0])),
            cur_tok=state.cur_tok.at[slot].set(tok0),
            remaining=state.remaining.at[slot].set(rem))

    def _prefill_request(self, req: Request):
        """Solo prefill into a fresh batch=1 state; returns (state1, tok0)."""
        return _solo_prefill(self._prefill, self.params, req, self.n_max)

    # ------------------------------------------------------------- serving --
    def _init_state(self) -> SV.SlotState:
        return SV.init_slot_state(self.cfg, self.max_batch, self.n_max,
                                  prefill_budget=self.prefill_budget)

    def start(self) -> None:
        """(Re)initialize the serving loop state; pair with step_serve()."""
        self._state = self._init_state()
        self._slots = [None] * self.max_batch
        self._done = []
        self.quarantined = []
        self._filling = None
        # uids are per-run: drop cancels left over from a previous run
        # (a finished uid must not ambush a later request reusing it),
        # but keep cancel-before-run requests aimed at the current queue
        self._cancelled &= {r.uid for r in self.queue}

    def pending(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self._slots)

    # -- loop phases (shared shape with the paged engine) --------------------
    def _finish_request(self, req: Request, t_now: float) -> None:
        _finalize_output(req, self.eos_id, t_now)
        self._done.append(req)

    def _evict_device(self, slot: int) -> None:
        """Deactivate a slot on-device (cancel path)."""
        self._state = self._cancel_fn(self._state, jnp.int32(slot))

    def _process_cancellations(self) -> None:
        if not self._cancelled:
            return
        t_now = time.perf_counter()
        for req in [r for r in self.queue if r.uid in self._cancelled]:
            self.queue.remove(req)
            req.cancelled = True
            req._tokens, req.token_times = [], []
            req._t_first = req.t_admit = t_now
            self._finish_request(req, t_now)
            self._cancelled.discard(req.uid)
        for slot, req in enumerate(self._slots):
            if req is None or req.uid not in self._cancelled:
                continue
            req.cancelled = True
            self._evict_device(slot)
            if not req._t_first:
                req._t_first = t_now
            self._finish_request(req, t_now)
            self._slots[slot] = None
            if self._filling == slot:
                self._filling = None
            self._cancelled.discard(req.uid)
        # leftovers match nothing in the queue or the slots: the request
        # already finished (or was never submitted) — a stale uid must not
        # ambush a later request that happens to reuse it
        self._cancelled.clear()

    # -- quarantine (ISSUE 10) -----------------------------------------------
    def _quarantine(self, slot: int, req: Request, exc: Exception) -> None:
        """Evict and fail exactly one request after an exception
        attributable to its slot: device state frozen and reclaimed
        (blocks, staging residency, histogram row — the same full path
        ``cancel()`` uses), output finalized from whatever tokens were
        already emitted, and the request recorded in both ``quarantined``
        and the done list. The rest of the batch keeps serving."""
        t_now = time.perf_counter()
        req.failed = True
        req.error = f"{type(exc).__name__}: {exc}"
        self._evict_device(slot)
        if req._tokens is None:
            req._tokens, req.token_times = [], []
        if not req._t_first:
            req._t_first = t_now
        self._finish_request(req, t_now)
        self._slots[slot] = None
        if self._filling == slot:
            self._filling = None
        self.quarantined.append(req)

    def _quarantine_admission(self, slot: int, req: Request,
                              exc: Exception) -> None:
        """Admission-path quarantine: the request never went live on a
        device slot, so only its reservations are unwound
        (``_abort_admit``) and it finishes failed with empty output."""
        t_now = time.perf_counter()
        req.failed = True
        req.error = f"{type(exc).__name__}: {exc}"
        if req._tokens is None:
            req._tokens, req.token_times = [], []
        req._t_first = t_now
        self._finish_request(req, t_now)
        self._abort_admit(slot)
        self.quarantined.append(req)

    # -- admission hooks (paged engine overrides) ----------------------------
    def _can_admit(self) -> bool:
        """Backpressure gate for the request at the head of the queue."""
        return True

    def _pre_admit(self, slot: int, req: Request) -> None:
        """Reserve engine resources for an admission (paged: blocks)."""

    def _abort_admit(self, slot: int) -> None:
        """Undo _pre_admit for a request that finished at prefill."""

    def _install_solo(self, slot: int, req: Request, state1, tok0) -> None:
        """Scatter a solo-prefill result into the device slot."""
        self._state = self._admit_fn(
            self._state, jnp.int32(slot), state1.caches, state1.regions,
            jnp.int32(tok0), jnp.int32(req.max_new_tokens - 1))

    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self._slots[slot] is not None or not self.queue:
                continue
            if not self._can_admit():
                break                        # backpressure: head waits
            if self.prefill_budget and self._filling is not None:
                break                        # at most one filling slot
            req = self.queue.pop(0)
            req.t_admit = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN_ADMIT, uid=req.uid):
                self._admit_one(slot, req)

    def _admit_one(self, slot: int, req: Request) -> None:
        """Admit ``req`` (just popped off the queue) into ``slot``."""
        if self.prefill_budget:
            self._pre_admit(slot, req)
            self._admit_chunked(slot, req)
            return
        try:
            self._pre_admit(slot, req)
            state1, tok0 = self._prefill_request(req)
        except Exception as exc:  # noqa: BLE001 — quarantine boundary
            self._quarantine_admission(slot, req, exc)
            return
        t_first = time.perf_counter()
        req.ttft_s = t_first - req.t_admit
        req._t_first = t_first
        req._tokens = [tok0]
        req.token_times = [t_first]
        if req.max_new_tokens <= 1 or tok0 == self.eos_id:
            req.output = np.asarray(req._tokens, np.int32)
            req.decode_s = 0.0
            self._done.append(req)
            self._abort_admit(slot)
            return
        try:
            self._install_solo(slot, req, state1, tok0)
        except Exception as exc:  # noqa: BLE001 — quarantine boundary
            self._quarantine_admission(slot, req, exc)
            return
        self._slots[slot] = req

    def _admit_chunked(self, slot: int, req: Request) -> None:
        """Chunked-prefill admission: copy the prompt to the device buffer
        and arm the slot's fill state — the decode chunk does the work."""
        req._tokens, req.token_times = [], []
        prow = np.zeros((self.n_max + self.prefill_budget,), np.int32)
        prow[:len(req.prompt)] = req.prompt
        self._state = self._admit_fill_fn(
            self._state, jnp.int32(slot), jnp.asarray(prow),
            jnp.int32(len(req.prompt)), jnp.int32(req.max_new_tokens))
        self._slots[slot] = req
        self._filling = slot

    def _pre_chunk_slot(self, slot: int, req: Request) -> None:
        """Per-slot pre-chunk host work (paged: lazy block allocation).
        An injected ``engine.slot`` fault fires here; any exception this
        raises is attributable to exactly one request and quarantines it."""
        if self.faults is not None:
            self.faults.apply("engine.slot", slot=slot, uid=req.uid)

    def _pre_chunk(self) -> None:
        """Per-chunk host bookkeeping, one slot at a time behind a
        quarantine boundary: a failure attributable to one slot evicts
        and fails that request while the rest of the batch keeps going."""
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            try:
                self._pre_chunk_slot(slot, req)
            except Exception as exc:  # noqa: BLE001 — quarantine boundary
                self._quarantine(slot, req, exc)

    def chunk_args(self) -> tuple:
        """The arguments of the next decode chunk: params, the live
        SlotState (donated by the chunk) and, for the paged engines, the
        block tables (+ the staging map when offloaded). Needs start()."""
        return (self.params, self._state)

    def lower_chunk(self):
        """Lower the next decode chunk for inspection — ``.compile()``
        gives the program the device runs. Needs start()."""
        return self._chunk.lower(*self.chunk_args())

    def _run_chunk(self):
        with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
            tokens, self._state = self._chunk(*self.chunk_args())
        with jax.profiler.TraceAnnotation(SPAN_WAIT):
            return np.asarray(tokens), np.asarray(self._state.remaining)

    def _release_slot(self, slot: int) -> None:
        """Hook: reclaim a finished slot's resources (paged: blocks)."""

    def _collect_slot(self, slot: int, req: Request, tokens: np.ndarray,
                      rem_after: np.ndarray, t_now: float) -> None:
        had = len(req._tokens)
        n_emit = _collect_chunk_row(req, tokens[slot], t_now)
        if had == 0 and n_emit > 0:          # chunked fill completed
            req.ttft_s = t_now - req.t_admit
            req._t_first = t_now
            if self._filling == slot:
                self._filling = None
                self._fill_complete(slot, req)
        self._after_collect(slot, req)
        if rem_after[slot] <= 0:
            self._finish_request(req, t_now)
            self._slots[slot] = None
            self._release_slot(slot)
            if self._filling == slot:        # safety: eos on first token
                self._filling = None

    def _collect(self, tokens: np.ndarray, rem_after: np.ndarray) -> None:
        t_now = time.perf_counter()
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            try:
                self._collect_slot(slot, req, tokens, rem_after, t_now)
            except Exception as exc:  # noqa: BLE001 — quarantine boundary
                if self._slots[slot] is None:
                    # the slot was already released (failure mid-cleanup):
                    # reclamation is no longer attributable — propagate
                    raise
                self._quarantine(slot, req, exc)

    def _after_collect(self, slot: int, req: Request) -> None:
        """Hook: host-side position tracking (paged allocator)."""

    def _fill_complete(self, slot: int, req: Request) -> None:
        """Hook: a chunked fill just finished — the slot's prompt blocks
        are fully written and immutable (paged sharing registers them)."""

    def step_serve(self) -> None:
        """One serving round: cancellations → admission → one decode chunk
        (a single host sync) → collection/eviction."""
        self._process_cancellations()
        self._admit()
        self.peak_concurrency = max(
            self.peak_concurrency,
            sum(r is not None for r in self._slots))
        if all(r is None for r in self._slots):
            return      # everything finished at prefill; maybe more queued
        with jax.profiler.TraceAnnotation(SPAN_PRE_CHUNK):
            self._pre_chunk()
        tokens, rem_after = self._run_chunk()
        with jax.profiler.TraceAnnotation(SPAN_COLLECT):
            self._collect(tokens, rem_after)

    def run(self) -> List[Request]:
        """Serve everything in the queue; returns completed requests."""
        self.start()
        while self.pending():
            self.step_serve()
        return self._done

    # ------------------------------------------------------------ teardown --
    def close(self) -> None:
        """Release engine-owned host resources deterministically
        (offloaded engine: fetch-pipeline executor + host pool guard
        threads). Idempotent; the resident engines hold none."""

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


class PagedServingEngine(ServingEngine):
    """Continuous batching over a paged KV cache (see module docstring).

    Memory knobs:
      * ``block_size``   — tokens per block (~128 on real hardware; small
        powers of two in tests). ``n_max`` must be a multiple of it.
      * ``num_blocks``   — size of the shared physical pool. Default
        ``max_batch * n_max // block_size`` reproduces the contiguous
        engine's footprint; the interesting regime is *smaller* pools
        with *more* slots, where admission is block-bound, not slot-bound.

    Scheduling is the slot engine's (solo bucket prefill — or chunked
    prefill with ``prefill_budget > 0`` — chunked decode, mid-flight
    eviction) with three paging twists:
      * admission requires ``⌈(prompt+gen)/block_size⌉`` unreserved blocks
        (FIFO honest backpressure — the head of the queue waits rather
        than being skipped);
      * physical blocks are handed to a slot lazily, right before the
        chunk whose appends will reach them (a chunk-filling slot gets
        its prompt blocks at admission — the fill writes through the
        block table from the first mixed step);
      * eviction returns the slot's blocks to the free list (zeroed),
        along with its incremental-histogram rows — including mid-fill
        eviction via ``cancel()``.

    ``share_prefixes=True`` (requires ``prefill_budget > 0``; ParisKV
    attention and MLA architectures — ``models.serve.share_supported``) adds
    **block-granular prefix sharing** (ISSUE 7): completed prompt blocks
    register in a chained-content-hash ``prefix_index``, later
    admissions map matching blocks straight into their table (refcount++,
    no fill pass — histograms rebuild from the shared blocks' metadata),
    and only the unshared suffix chunk-fills. The block holding the last
    prompt token stays private (it takes the fill's final tokens and the
    decode appends — copy-on-write by construction), and a shared block
    is zeroed/freed only when its refcount hits 0. Backpressure
    reservation counts only the blocks an admission will actually draw
    from the pool, so a fleet sharing one system prompt admits at
    near-flat block cost.

    ``offload=True`` (with ``num_device_blocks`` / ``prefetch`` /
    ``prefetch_hook``) constructs an :class:`OffloadedPagedServingEngine`
    instead: the full K/V pool moves to host memory and the device keeps
    retrieval metadata plus a bounded staging pool (ISSUE 6).

    ``mesh_shards=N`` (ISSUE 8) serves over an N-device 1-D mesh that
    partitions the pool, retrieval metadata and histograms on the KV-head
    axis: Stage I/II run shard-local inside ``shard_map`` and only
    attention-output heads are all-gathered, so tokens are bit-identical
    to the single-device engine while each device holds ``1/N`` of the
    pool bytes — at a fixed per-device budget, ``num_blocks`` (and the
    admissible batch) scales with N. Requires ``num_kv_heads % N == 0``
    and N visible devices (CPU: ``XLA_FLAGS=
    --xla_force_host_platform_device_count=N``); mesh+offload and
    mesh+MLA raise :class:`models.serve.UnsupportedShardedConfig`.
    """

    def __new__(cls, *args, **kwargs):
        if cls is PagedServingEngine and kwargs.get("offload"):
            return super().__new__(OffloadedPagedServingEngine)
        return super().__new__(cls)

    def __init__(self, cfg: ModelConfig, params, n_max: int = 4096,
                 max_batch: int = 8, block_size: int = CC.PAGED_DEFAULT_BLOCK,
                 num_blocks: Optional[int] = None, greedy: bool = True,
                 use_pariskv: bool = True, chunk_size: int = 8,
                 eos_id: Optional[int] = None, fused: bool = True,
                 prefill_budget: int = 0, offload: bool = False,
                 share_prefixes: bool = False, mesh_shards: int = 1,
                 faults: Optional[FaultPlan] = None):
        assert use_pariskv, "the paged engine serves the ParisKV path only"
        if n_max % block_size != 0:
            raise ValueError(f"n_max={n_max} must be a multiple of "
                             f"block_size={block_size}")
        if mesh_shards > 1:
            if cfg.num_kv_heads % mesh_shards != 0:
                raise ValueError(
                    f"mesh_shards={mesh_shards} must divide num_kv_heads="
                    f"{cfg.num_kv_heads}: the mesh partitions whole KV "
                    f"heads, and an uneven split would give shards "
                    f"different pool shapes")
            if jax.device_count() < mesh_shards:
                raise ValueError(
                    f"mesh_shards={mesh_shards} needs {mesh_shards} "
                    f"devices but jax sees {jax.device_count()} — on CPU "
                    f"set XLA_FLAGS=--xla_force_host_platform_device_count"
                    f"={mesh_shards} before importing jax")
            reason = SV.sharded_support_reason(cfg)
            if reason is not None:
                raise SV.UnsupportedShardedConfig(
                    cfg, f"mesh_shards={mesh_shards}", reason)
        if share_prefixes:
            if prefill_budget <= 0:
                raise ValueError(
                    "share_prefixes=True requires prefill_budget > 0: the "
                    "shared prefix is *skipped* by the chunked fill, and "
                    "solo prefill has no way to resume past it")
            reason = SV.share_support_reason(cfg)
            if reason is not None:
                raise ValueError(f"prefix sharing unavailable — {reason}")
        super().__init__(cfg, params, n_max=n_max, max_batch=max_batch,
                         greedy=greedy, use_pariskv=True,
                         chunk_size=chunk_size, eos_id=eos_id,
                         prefill_budget=prefill_budget, faults=faults)
        self.block_size = block_size
        self.nblk = n_max // block_size
        self.num_blocks = (max_batch * self.nblk if num_blocks is None
                           else num_blocks)
        # fused=True (default): Stage-I/II run directly over the pool with
        # the incremental bucket histogram — no per-step paged_meta_view
        # copy (Pallas kernels on TPU, jnp twins elsewhere /
        # REPRO_PALLAS_INTERPRET=1). fused=False falls back to the
        # materialized-view path (token-identical at hist_sample=0; kept
        # for A/B and bisection).
        self.fused = fused
        self._chunk = jax.jit(
            lambda p, st, bt: SV.decode_chunk(p, cfg, st, chunk_size,
                                              eos_id=eos_id,
                                              block_tables=bt,
                                              paged_fused=fused,
                                              prefill_budget=prefill_budget),
            donate_argnums=(1,))
        self._admit_fn = jax.jit(
            lambda st, slot, pb, c1, r1, t0, rem: SV.admit_paged(
                st, slot, pb, c1, r1, t0, rem, pcfg=cfg.pariskv),
            donate_argnums=(0,))
        self._evict_fn = jax.jit(self._evict_impl, donate_argnums=(0,))
        self.share_prefixes = share_prefixes
        if share_prefixes:
            # the shared twin of admit_fill: fill_start is *traced*, so
            # one compiled shape serves hit and miss admissions alike
            self._admit_fill_fn = jax.jit(
                lambda st, slot, prow, ln, mn, bt, fs: SV.admit_fill(
                    st, slot, prow, ln, mn, fill_start=fs, bt_row=bt,
                    pcfg=cfg.pariskv),
                donate_argnums=(0,))

        # mesh-sharded serving (ISSUE 8): rewrap every jit that touches
        # SlotState in shard_map over a 1-D KV-head mesh. The state's pool/
        # metadata/hist leaves live sharded on device (sharded_state_specs);
        # everything else — params, block tables, solo-prefill results,
        # scalars — is replicated, and the allocator below is untouched:
        # block numbering is global, so admission reserves and eviction
        # reclaims the same physical blocks on every shard.
        self.mesh_shards = mesh_shards
        self.mesh = None
        if mesh_shards > 1:
            P_ = jax.sharding.PartitionSpec
            rep = P_()
            # Auto axes: the specs below place state by shard_map, and
            # values computed outside it (solo-prefill results) stay
            # replicated — Explicit axes would type-check them apart
            self.mesh = jax.make_mesh(
                (mesh_shards,), ("kv",),
                axis_types=(jax.sharding.AxisType.Auto,))
            dist = SV.ShardedPagedDist("kv", mesh_shards)
            ss = SV.sharded_state_specs(
                SV.make_paged_caches(cfg, max_batch, self.num_blocks,
                                     block_size, n_max, as_spec=True),
                prefill_budget=prefill_budget)
            self._state_specs = ss
            self._chunk = jax.jit(L.shard_map_compat(
                lambda p, st, bt: SV.decode_chunk(
                    p, cfg, st, chunk_size, eos_id=eos_id, block_tables=bt,
                    paged_fused=fused, prefill_budget=prefill_budget,
                    dist=dist),
                mesh=self.mesh, in_specs=(rep, ss, rep),
                out_specs=(rep, ss)),
                donate_argnums=(1,))
            self._admit_fn = jax.jit(L.shard_map_compat(
                lambda st, slot, pb, c1, r1, t0, rem: SV.admit_paged(
                    st, slot, pb, c1, r1, t0, rem, pcfg=cfg.pariskv,
                    dist=dist),
                mesh=self.mesh, in_specs=(ss,) + (rep,) * 6,
                out_specs=ss),
                donate_argnums=(0,))
            self._evict_fn = jax.jit(L.shard_map_compat(
                self._evict_impl, mesh=self.mesh, in_specs=(ss, rep, rep),
                out_specs=ss),
                donate_argnums=(0,))
            if share_prefixes:
                self._admit_fill_fn = jax.jit(L.shard_map_compat(
                    lambda st, slot, prow, ln, mn, bt, fs: SV.admit_fill(
                        st, slot, prow, ln, mn, fill_start=fs, bt_row=bt,
                        pcfg=cfg.pariskv),
                    mesh=self.mesh, in_specs=(ss,) + (rep,) * 6,
                    out_specs=ss),
                    donate_argnums=(0,))
            elif prefill_budget > 0:
                self._admit_fill_fn = jax.jit(L.shard_map_compat(
                    lambda st, slot, prow, ln, mn: SV.admit_fill(
                        st, slot, prow, ln, mn),
                    mesh=self.mesh, in_specs=(ss,) + (rep,) * 4,
                    out_specs=ss),
                    donate_argnums=(0,))
            # solo prefill runs replicated over the mesh (out_shardings)
            # so _admit_fn never mixes single-device and mesh arrays
            self._prefill = jax.jit(
                lambda p, t, lens, m: SV.prefill(p, cfg, t, n_max, m,
                                                 lengths=lens),
                out_shardings=jax.sharding.NamedSharding(self.mesh, rep))
            self.params = jax.device_put(
                params, jax.sharding.NamedSharding(self.mesh, rep))

        # host-side allocator state (deque: _take_block pops the head —
        # O(1), unlike list.pop(0)'s O(n) shuffle)
        self._free: Deque[int] = collections.deque(range(self.num_blocks))
        self._alloc: Dict[int, List[int]] = {}   # slot → physical blocks
        self._resv: Dict[int, int] = {}          # slot → unallocated reserve
        self._pos: Dict[int, int] = {}           # slot → host view of pos
        self._need: Dict[int, int] = {}          # slot → total token budget
        self._bt = np.full((max_batch, self.nblk), -1, np.int32)
        # prefix-sharing state (ISSUE 7). Refcounts are maintained even
        # with sharing off (every block then holds exactly one reference)
        # so there is a single reclamation path to get right.
        self._refcnt: Dict[int, int] = {}        # phys block → live holders
        self._prefix_index: Dict[bytes, int] = {}  # chained hash → block
        self._block_hash: Dict[int, bytes] = {}  # reverse map (unregister)
        self._fill_start: Dict[int, int] = {}    # slot → shared-prefix end
        self.blocks_consumed = 0   # fresh blocks drawn from the pool (ever)
        self.shared_block_hits = 0  # admissions served by mapping, not fill

    # ------------------------------------------------------------ helpers --
    def blocks_needed(self, req: Request) -> int:
        return -(-(len(req.prompt) + req.max_new_tokens) // self.block_size)

    @property
    def free_blocks(self) -> int:
        """Blocks neither allocated nor reserved — admission headroom."""
        return len(self._free) - sum(self._resv.values())

    @staticmethod
    def _evict_impl(state: SV.SlotState, phys_blocks, slot):
        """Zero a reclaimed slot's pool blocks (hygiene: masks already stop
        stale reads, but reclaimed blocks shouldn't leak tenant K/V) and
        its incremental bucket histogram (so a freed slot's hist is
        all-zero until the next admission recomputes it)."""
        def clear(key, entry):
            if isinstance(entry, CC.PagedLayerKVCache):
                return CC.paged_clear_blocks(entry, phys_blocks)
            if key == "hist":
                zero = jnp.zeros_like(entry[:, :1])
                return jax.lax.dynamic_update_slice_in_dim(
                    entry, zero, slot, axis=1)
            return entry
        caches = [
            {ln: {key: clear(key, lc[key]) for key in lc}
             for ln, lc in stage.items()}
            for stage in state.caches]
        return state._replace(caches=caches)

    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.n_max:
            raise ValueError(
                f"request {req.uid}: prompt {len(req.prompt)} + "
                f"{req.max_new_tokens} new tokens exceeds n_max={self.n_max}")
        if self.blocks_needed(req) > self.num_blocks:
            raise ValueError(
                f"request {req.uid}: needs {self.blocks_needed(req)} blocks, "
                f"pool holds {self.num_blocks} — request can never run")
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def _take_block(self, slot: int) -> None:
        blk = self._free.popleft()
        self._bt[slot, len(self._alloc[slot])] = blk
        self._alloc[slot].append(blk)
        self._resv[slot] -= 1
        self._refcnt[blk] = 1
        self.blocks_consumed += 1

    # ------------------------------------------ prefix sharing (ISSUE 7) ---
    def _chain_hashes(self, prompt) -> List[bytes]:
        """Chained content hash per *shareable* full prompt block: block i
        hashes its token ids together with block i-1's digest, so equal
        hashes mean equal tokens AND equal preceding context — exactly
        the condition under which the cached K/V is reusable. The block
        containing the LAST prompt token is excluded: it must stay
        private (the fill needs ≥ 1 token to produce first-token logits,
        and decode appends may land in it — the copy-on-write tail)."""
        toks = np.ascontiguousarray(np.asarray(prompt, np.int32))
        bs = self.block_size
        out: List[bytes] = []
        h = b""
        for i in range((len(toks) - 1) // bs):
            h = hashlib.sha256(h + toks[i * bs:(i + 1) * bs].tobytes()
                               ).digest()
            out.append(h)
        return out

    def _lookup_shared(self, req: Request) -> List[int]:
        """Longest already-cached prefix of the request's shareable
        blocks, as physical block ids (possibly empty)."""
        blocks: List[int] = []
        for hh in self._chain_hashes(req.prompt):
            blk = self._prefix_index.get(hh)
            if blk is None:
                break
            blocks.append(blk)
        return blocks

    def _map_shared(self, slot: int, blk: int) -> None:
        """Map an already-cached block into the slot's table: refcount++,
        no pool draw — the reservation made for it is released."""
        self._bt[slot, len(self._alloc[slot])] = blk
        self._alloc[slot].append(blk)
        self._resv[slot] -= 1
        self._refcnt[blk] += 1
        self.shared_block_hits += 1

    def _decref_blocks(self, slot: int) -> List[int]:
        """Drop the slot's references; return the blocks that died (their
        last holder left — only these may be zeroed/freed/unindexed)."""
        dead: List[int] = []
        for blk in self._alloc.get(slot, ()):
            self._refcnt[blk] -= 1
            if self._refcnt[blk] == 0:
                del self._refcnt[blk]
                hh = self._block_hash.pop(blk, None)
                if hh is not None:
                    self._prefix_index.pop(hh, None)
                dead.append(blk)
        return dead

    def _dead_row(self, dead: List[int]) -> jnp.ndarray:
        """Refcount-0 blocks as an eviction row (same (nblk,) shape as
        ``_phys_row`` — one `_evict_fn` compile serves both), padded with
        out-of-range sentinels so still-shared blocks are never zeroed."""
        phys = np.full((self.nblk,), self.num_blocks, np.int32)
        phys[:len(dead)] = dead
        return jnp.asarray(phys)

    def _fill_complete(self, slot: int, req: Request) -> None:
        """Register the finished fill's shareable blocks in the prefix
        index (first writer wins; a sharer re-registering its mapped
        prefix is a no-op). Until now the blocks were partially written —
        registering at completion is what keeps a concurrent identical
        prompt from mapping garbage."""
        if not self.share_prefixes:
            return
        for i, hh in enumerate(self._chain_hashes(req.prompt)):
            blk = int(self._bt[slot, i])
            if hh not in self._prefix_index:
                self._prefix_index[hh] = blk
                self._block_hash[blk] = hh

    def _ensure_blocks(self, slot: int) -> None:
        """Lazy allocation: before a chunk, give ``slot`` every block its
        appends can reach (positions ≤ pos + chunk_size), capped by its
        admission-time reservation."""
        upto = min(self._pos[slot] + 1 + self.chunk_size, self._need[slot])
        nb = min(-(-upto // self.block_size),
                 len(self._alloc[slot]) + self._resv[slot])
        while len(self._alloc[slot]) < nb:
            self._take_block(slot)

    def _phys_row(self, slot: int) -> jnp.ndarray:
        """Slot's block-table row as physical ids with out-of-bounds
        sentinels (num_blocks) at unallocated entries — scatter-droppable."""
        phys = np.full((self.nblk,), self.num_blocks, np.int32)
        row = self._bt[slot]
        phys[row >= 0] = row[row >= 0]
        return jnp.asarray(phys)

    def _reserve_blocks(self, slot: int, req: Request) -> None:
        """Worst-case block reservation + upfront allocation of the
        prompt's blocks (both admission paths write the whole prompt —
        solo in one scatter, chunked through the table from step one).

        With prefix sharing, already-cached prefix blocks are *mapped*
        first (refcount++, no pool draw — their reservation is released
        on the spot), then only the unshared prompt blocks are taken;
        ``_fill_start[slot]`` records where the chunked fill resumes."""
        self._alloc[slot] = []
        self._resv[slot] = self.blocks_needed(req)
        self._pos[slot] = len(req.prompt) - 1
        self._need[slot] = len(req.prompt) + req.max_new_tokens
        shared = self._lookup_shared(req) if self.share_prefixes else []
        for blk in shared:
            self._map_shared(slot, blk)
        self._fill_start[slot] = len(shared) * self.block_size
        req.shared_prefix_blocks = len(shared)
        for _ in range(-(-len(req.prompt) // self.block_size) - len(shared)):
            self._take_block(slot)

    def _release_host(self, slot: int,
                      dead: Optional[List[int]] = None) -> None:
        """Drop the slot's block references and return the *dead* ones
        (refcount 0 — ``dead``, or computed here) to the free list; a
        block some other slot still maps survives in place, index entry
        and all, until its last holder exits."""
        if dead is None:
            dead = self._decref_blocks(slot)
        self._alloc.pop(slot, None)
        self._free.extend(dead)
        self._resv.pop(slot, None)
        self._pos.pop(slot, None)
        self._need.pop(slot, None)
        self._fill_start.pop(slot, None)
        self._bt[slot] = -1

    # ------------------------------------------- loop phases (overrides) ----
    def _init_state(self) -> SV.SlotState:
        state = SV.init_paged_slot_state(
            self.cfg, self.max_batch, self.num_blocks, self.block_size,
            self.n_max, prefill_budget=self.prefill_budget)
        if self.mesh is None:
            return state
        return jax.tree.map(
            lambda x, s: jax.device_put(
                x, jax.sharding.NamedSharding(self.mesh, s)),
            state, self._state_specs)

    def _evict_device(self, slot: int) -> None:
        """Cancel path: freeze the slot, zero + reclaim its dead blocks
        and hist row (still-shared blocks survive for their holders)."""
        self._state = self._cancel_fn(self._state, jnp.int32(slot))
        dead = self._decref_blocks(slot)
        self._state = self._evict_fn(self._state, self._dead_row(dead),
                                     jnp.int32(slot))
        self._release_host(slot, dead=dead)

    def _can_admit(self) -> bool:
        need = self.blocks_needed(self.queue[0])
        if self.share_prefixes:
            # shared prefix blocks are mapped, never drawn from the pool —
            # the head only waits for the blocks it will actually consume
            need -= len(self._lookup_shared(self.queue[0]))
        return need <= self.free_blocks

    def _pre_admit(self, slot: int, req: Request) -> None:
        self._reserve_blocks(slot, req)

    def _abort_admit(self, slot: int) -> None:
        self._release_host(slot)  # pool untouched: host-only

    def _install_solo(self, slot: int, req: Request, state1, tok0) -> None:
        self._state = self._admit_fn(
            self._state, jnp.int32(slot), self._phys_row(slot),
            state1.caches, state1.regions, jnp.int32(tok0),
            jnp.int32(req.max_new_tokens - 1))

    def _pre_chunk_slot(self, slot: int, req: Request) -> None:
        super()._pre_chunk_slot(slot, req)     # engine.slot fault hook
        self._ensure_blocks(slot)

    def chunk_args(self) -> tuple:
        return (self.params, self._state, jnp.asarray(self._bt))

    def _after_collect(self, slot: int, req: Request) -> None:
        # host view of the device pos: last prompt token + decoded tokens
        # (during a fill: still the prompt end — its blocks are allocated)
        self._pos[slot] = (len(req.prompt) - 1
                           + max(0, len(req._tokens) - 1))

    def _admit_chunked(self, slot: int, req: Request) -> None:
        """Sharing: admit with the fill frontier past the mapped prefix —
        the block-table row (with its -1 sentinels) rides along so the
        slot's histogram can rebuild from the shared blocks' metadata."""
        if not self.share_prefixes:
            return super()._admit_chunked(slot, req)
        req._tokens, req.token_times = [], []
        prow = np.zeros((self.n_max + self.prefill_budget,), np.int32)
        prow[:len(req.prompt)] = req.prompt
        self._state = self._admit_fill_fn(
            self._state, jnp.int32(slot), jnp.asarray(prow),
            jnp.int32(len(req.prompt)), jnp.int32(req.max_new_tokens),
            jnp.asarray(self._bt[slot]),
            jnp.int32(self._fill_start.get(slot, 0)))
        self._slots[slot] = req
        self._filling = slot

    def _release_slot(self, slot: int) -> None:
        dead = self._decref_blocks(slot)
        self._state = self._evict_fn(self._state, self._dead_row(dead),
                                     jnp.int32(slot))
        self._release_host(slot, dead=dead)

    # ------------------------------------------ invariant auditor (ISSUE 10)
    @staticmethod
    def _check(cond: bool, msg: str) -> None:
        if not cond:
            raise InvariantViolation(msg)

    def verify_invariants(self, check_hist: bool = True) -> None:
        """Cross-check the engine's redundant state against itself; raise
        :class:`InvariantViolation` on the first inconsistency.

        Audited at any chunk boundary (between ``step_serve`` calls):

        * free-list accounting — no duplicate free blocks, free ∩
          allocated = ∅, and every pool block is exactly one of
          free / allocated-to-some-slot;
        * block-table / refcount consistency — ``_bt`` rows mirror
          ``_alloc``, each block's refcount equals its live holders, and
          the prefix index ↔ block-hash maps stay a bijection over
          allocated blocks;
        * (``check_hist``) the incremental bucket histogram of every
          active, non-filling slot equals a from-scratch recompute from
          the pool metadata through its block table — the retrieval
          correctness anchor (a drifted histogram silently re-ranks
          Stage I)."""
        alloc_sets = {s: list(b) for s, b in self._alloc.items()}
        allocated: Dict[int, int] = {}
        for s, blks in alloc_sets.items():
            self._check(len(set(blks)) == len(blks),
                        f"slot {s} holds a duplicate block: {blks}")
            for b in blks:
                allocated[b] = allocated.get(b, 0) + 1
        free = list(self._free)
        self._check(len(set(free)) == len(free),
                    "free list holds duplicate blocks")
        self._check(not (set(free) & set(allocated)),
                    "free list intersects allocated blocks: "
                    f"{sorted(set(free) & set(allocated))}")
        self._check(len(free) + len(allocated) == self.num_blocks,
                    f"block accounting leak: {len(free)} free + "
                    f"{len(allocated)} allocated != {self.num_blocks}")
        self._check(set(self._refcnt) == set(allocated),
                    "refcount keys drifted from allocated blocks")
        for b, n in allocated.items():
            self._check(self._refcnt.get(b) == n,
                        f"block {b}: refcount {self._refcnt.get(b)} != "
                        f"{n} live holders")
        for s, n in self._resv.items():
            self._check(n >= 0, f"slot {s}: negative reservation {n}")
        for slot in range(self.max_batch):
            row = self._bt[slot]
            want = alloc_sets.get(slot, [])
            got = row[row >= 0].tolist()
            self._check(got == want,
                        f"slot {slot}: block-table row {got} != "
                        f"allocator view {want}")
        self._check(set(self._prefix_index.values())
                    == set(self._block_hash), "prefix index / block-hash "
                    "maps are not a bijection")
        for hh, b in self._prefix_index.items():
            self._check(self._block_hash.get(b) == hh,
                        f"block {b}: hash map disagrees with prefix index")
            self._check(b in self._refcnt,
                        f"prefix index retains unallocated block {b}")
        if check_hist and self._state is not None:
            self._verify_hist()

    def _verify_hist(self) -> None:
        """hist == recompute, per pariskv entry and repeat, for every
        active non-filling slot (a mid-fill hist is exact against the
        *fill frontier*, which the recompute below cannot see; inactive
        slots may sit on stale regions, so only live rows are audited)."""
        audit = [s for s, rq in enumerate(self._slots)
                 if rq is not None and s != self._filling]
        if not audit:
            return
        pcfg = self.cfg.pariskv
        n_log = self.nblk * self.block_size
        btj = jnp.asarray(np.clip(self._bt, 0, None))
        valid = CC.retrieval_valid_mask(n_log, self._state.regions, pcfg)
        for si, stage in enumerate(self._state.caches):
            for ln, lc in stage.items():
                if "hist" not in lc or not isinstance(
                        lc["kv"], CC.PagedLayerKVCache):
                    continue
                hist = np.asarray(lc["hist"])
                for r in range(hist.shape[0]):
                    pool_r = jax.tree.map(lambda a: a[r], lc["kv"])
                    ids, _, _ = CC.paged_meta_view(pool_r, btj)
                    want = np.asarray(R.bucket_histogram(
                        ids, valid[:, None, :], pcfg.num_centroids()))
                    for slot in audit:
                        if not np.array_equal(hist[r, slot], want[slot]):
                            raise InvariantViolation(
                                f"stage {si} layer {ln} repeat {r} slot "
                                f"{slot}: incremental histogram drifted "
                                f"from pool-metadata recompute")

    def run(self) -> List[Request]:
        done = super().run()
        assert len(self._free) == self.num_blocks, \
            "block leak: allocator did not reclaim every block"
        assert not self._refcnt and not self._prefix_index, \
            "refcount leak: blocks still referenced after run"
        return done


class OffloadedPagedServingEngine(PagedServingEngine):
    """Paged serving over the **tiered host-offloaded pool** (ISSUE 6).

    Device HBM holds all retrieval metadata (ids/codes/weights + per-slot
    bucket histograms) plus a bounded staging pool of
    ``num_device_blocks`` K/V blocks; the full K/V pool lives host-side
    (serving.offload_lib.HostKVPool — the CPU analogue of the paper's
    host-offloaded tier, fetched via ``pure_callback`` instead of async
    ``device_put``). Each decode step runs Stage I/II on device exactly
    as the resident engine; winners resolve against the residency map
    (``dev_map``): staging hits gather on device, misses fetch from the
    host pool mid-step. Token-identical to ``PagedServingEngine`` by
    construction — residency decides *where* a winner's bytes come from,
    never *which* winners attend.

    Residency changes only at chunk boundaries:
      * every block a chunk may **write or must read densely** (sink +
        local window + append/fill frontier) is pinned staging-resident —
        required blocks not already staged are fetched synchronously
        (the prediction-miss fallback);
      * ``prefetch=True`` additionally stages the previous chunk's
        hottest winner blocks (FreeKV-style chunk-boundary prefetch;
        ``prefetch_hook(touched, k)`` overrides the predictor — a wrong
        hook costs bytes, not tokens);
      * staging slots recycle by second-chance clock over unpinned
        blocks; evicted blocks write back to the host pool first (blocks
        are K/V-immutable once the write frontier passes, so the copy is
        final).

    Admission prefills solo at the *prompt's* bucketed capacity (not
    ``n_max`` — device peak stays independent of logical context), writes
    prompt K/V straight into the host pool, and scatters only metadata +
    histogram to the device (``models.serve.admit_tiered``). Eviction and
    ``cancel(uid)`` reclaim both tiers: host blocks zeroed, staging slots
    freed without write-back (the data is dead).

    The fetch discipline is the **overlapped pipeline** by default
    (ISSUE 9): one coalesced, deduped begin/collect callback pair per
    pariskv layer per step, with the host gather running on a worker
    thread while the step's dense attention work proceeds between the
    two callbacks. ``overlap=False`` is the synchronous single-callback
    escape hatch (the PR-5 path) for A/B and debugging — tokens are
    bit-identical either way; only schedule and stall move.

    Per-request fetch observability lands on ``Request``: staging_hits/
    staging_misses (winner head-rows by serving tier), fetched_bytes /
    fetched_unique_bytes (on-demand host→device traffic, requested vs
    after-dedup), prefetched_blocks/prefetch_hits (prediction
    accuracy), and fetch_stall_s/fetch_callbacks (pipeline residual
    stall and callback count, attributed ∝ fetch rows).
    """

    def __init__(self, cfg: ModelConfig, params, n_max: int = 4096,
                 max_batch: int = 8, block_size: int = CC.PAGED_DEFAULT_BLOCK,
                 num_blocks: Optional[int] = None, greedy: bool = True,
                 use_pariskv: bool = True, chunk_size: int = 8,
                 eos_id: Optional[int] = None, fused: bool = True,
                 prefill_budget: int = 0, offload: bool = True,
                 num_device_blocks: Optional[int] = None,
                 prefetch: bool = True, prefetch_hook=None,
                 overlap: bool = True,
                 share_prefixes: bool = False, mesh_shards: int = 1,
                 fetch_timeout_s: Optional[float] = None,
                 fetch_max_retries: int = 2,
                 fetch_backoff_s: float = 0.005,
                 faults: Optional[FaultPlan] = None):
        if mesh_shards > 1:
            raise SV.UnsupportedShardedConfig(
                cfg, f"offload=True with mesh_shards={mesh_shards}",
                "the tiered host pool fetches K/V through single-device "
                "pure_callback reads — shard the resident engine "
                "(offload=False) instead (ROADMAP)")
        reason = SV.offload_support_reason(cfg)
        if reason is not None:
            raise ValueError(f"offloaded paged serving unavailable — "
                             f"{reason}")
        super().__init__(cfg, params, n_max=n_max, max_batch=max_batch,
                         block_size=block_size, num_blocks=num_blocks,
                         greedy=greedy, use_pariskv=use_pariskv,
                         chunk_size=chunk_size, eos_id=eos_id, fused=fused,
                         prefill_budget=prefill_budget,
                         share_prefixes=share_prefixes, faults=faults)
        self.num_device_blocks = (max(1, self.num_blocks // 4)
                                  if num_device_blocks is None
                                  else num_device_blocks)
        self.prefetch = prefetch
        self.prefetch_hook = prefetch_hook
        # pariskv cache-entry registry: (stage idx, layer key, host name)
        self._entries: List[tuple] = []
        shapes = {}
        for si, stage in enumerate(SV.layer_plan(cfg)):
            for i, ld in enumerate(stage.layers):
                if ld.mixer in ("attn", "hybrid") and ld.use_pariskv:
                    name = f"s{si}.l{i}"
                    self._entries.append((si, f"l{i}", name))
                    shapes[name] = (stage.repeat, cfg.num_kv_heads,
                                    cfg.head_dim)
        # NB: the jitted chunk closes over this exact HostKVPool object
        # (its bound-method callbacks are the pure_callback targets) —
        # start() zeroes it in place rather than replacing it
        self.host = offload_lib.HostKVPool(shapes, self.num_blocks,
                                       self.block_size, SV._dtype(cfg))
        # fetch fault policy (ISSUE 10): deadline + bounded retries with
        # exponential backoff, shared by the sync and pipelined paths
        self.host.fetch_timeout_s = fetch_timeout_s
        self.host.fetch_max_retries = fetch_max_retries
        self.host.fetch_backoff_s = fetch_backoff_s
        self.host.faults = faults
        self.staging = offload_lib.StagingMap(self.num_blocks,
                                          self.num_device_blocks)
        self.overlap = bool(overlap)
        # layer-pass fetch entries the chunk will trace (used to
        # normalize the callbacks-per-layer-per-step invariant)
        self.num_fetch_layers = sum(
            shapes[name][0] for _, _, name in self._entries)
        self.pipeline = (offload_lib.FetchPipeline(self.host)
                         if self.overlap else None)
        # NB: like the pool, the jitted chunk closes over this exact
        # fetch object — start() resets it in place
        fetch = self.pipeline if self.overlap else self.host
        self._chunk = jax.jit(
            lambda p, st, bt, dm: SV.decode_chunk(
                p, cfg, st, chunk_size, eos_id=eos_id, block_tables=bt,
                paged_fused=fused, prefill_budget=prefill_budget,
                dev_map=dm, fetch=fetch),
            donate_argnums=(1,))
        # solo prefill at the prompt's bucketed capacity (static arg →
        # one compile per bucket), so admission never materializes an
        # n_max-sized contiguous cache on device
        self._prefill = jax.jit(
            lambda p, t, lens, m, cap: SV.prefill(p, cfg, t, cap, m,
                                                  lengths=lens),
            static_argnums=(4,))
        self._admit_fn = jax.jit(
            lambda st, slot, pb, c1, r1, t0, rem: SV.admit_tiered(
                st, slot, pb, c1, r1, t0, rem, pcfg=cfg.pariskv),
            donate_argnums=(0,))
        self._evict_fn = jax.jit(self._evict_tiered_impl,
                                 donate_argnums=(0,))
        self._stage_fn = jax.jit(self._stage_impl, donate_argnums=(0,))
        self._read_staging_fn = jax.jit(self._read_staging_impl)
        # exponential-decay touch score per host block — smoother
        # prefetch ranking than the last-chunk-only snapshot it replaces
        self._touched_last = np.zeros((self.num_blocks,), np.float64)
        self._touch_decay = 0.5
        self._last_prefetch: List[int] = []
        # engine-level stall trace: (stall seconds, callbacks) per chunk
        self.fetch_stall_chunks: List[tuple] = []
        self.fetch_stall_s = 0.0
        self.fetch_callbacks = 0
        # fault-tolerance totals (ISSUE 10)
        self.fetch_retries = 0
        self.fetch_timeouts = 0
        self.degraded_steps = 0      # degraded (layer, step) fetches
        self.storm_evictions = 0     # staging blocks flushed by storms
        # host unique-row counter snapshots for per-chunk deltas
        self._uniq_head = 0
        self._uniq_fill = 0

    # ------------------------------------------------------ device helpers --
    def _stage_impl(self, state: SV.SlotState, stag_blocks, payloads):
        """Install host block payloads into staging slots (all entries)."""
        caches = [dict(sc) for sc in state.caches]
        for si, ln, name in self._entries:
            lc = caches[si][ln]
            k, v = payloads[name]
            caches[si][ln] = {**lc, "kv": CC.tiered_stage_blocks(
                lc["kv"], stag_blocks, k, v)}
        return state._replace(caches=caches)

    def _read_staging_impl(self, state: SV.SlotState, stag_blocks):
        """Read staging blocks back for host write-back (pad ids are
        clipped — callers slice the valid prefix)."""
        safe = jnp.clip(stag_blocks, 0, self.num_device_blocks - 1)
        out = {}
        for si, ln, name in self._entries:
            kv = state.caches[si][ln]["kv"]
            out[name] = (kv.k[:, safe], kv.v[:, safe])
        return out

    def _evict_tiered_impl(self, state: SV.SlotState, meta_blocks,
                           stag_blocks, slot):
        """Tiered eviction hygiene: host-block ids zero the meta leaves,
        staging-slot ids zero the K/V leaves, and the slot's histogram
        row is cleared (same contract as the resident ``_evict_impl``)."""
        def clear(key, entry):
            if isinstance(entry, CC.PagedLayerKVCache):
                return CC.tiered_clear_blocks(entry, meta_blocks,
                                              stag_blocks)
            if key == "hist":
                zero = jnp.zeros_like(entry[:, :1])
                return jax.lax.dynamic_update_slice_in_dim(
                    entry, zero, slot, axis=1)
            return entry
        caches = [
            {ln: {key: clear(key, lc[key]) for key in lc}
             for ln, lc in stage.items()}
            for stage in state.caches]
        return state._replace(caches=caches)

    # ------------------------------------------------------------ admission --
    def _solo_cap(self, plen: int) -> int:
        """Bucketed prefill capacity: power-of-two prompt bucket rounded
        up to whole blocks, never above n_max."""
        b = _bucket(plen, cap=self.n_max)
        return min(self.n_max, -(-b // self.block_size) * self.block_size)

    def _prefill_request(self, req: Request):
        cap = self._solo_cap(len(req.prompt))
        s = _bucket(len(req.prompt), cap=cap)
        toks = np.zeros((1, s), np.int32)
        toks[0, :len(req.prompt)] = req.prompt
        lens = jnp.asarray([len(req.prompt)], jnp.int32)
        media = None
        if req.media is not None:
            media = jnp.asarray(req.media)[None]
        logits, state1 = self._prefill(self.params, jnp.asarray(toks), lens,
                                       media, cap)
        return state1, int(jnp.argmax(logits[0], -1))

    def _install_solo(self, slot: int, req: Request, state1, tok0) -> None:
        si0, ln0, _ = self._entries[0]
        cap = state1.caches[si0][ln0]["kv"].k.shape[2]  # (R, 1, cap, G, hd)
        phys = np.asarray(self._phys_row(slot))[:cap // self.block_size]
        for si, ln, name in self._entries:
            kv1 = state1.caches[si][ln]["kv"]
            self.host.write_prefill(name, phys, np.asarray(kv1.k)[:, 0],
                                    np.asarray(kv1.v)[:, 0])
        self._state = self._admit_fn(
            self._state, jnp.int32(slot), jnp.asarray(phys),
            state1.caches, state1.regions, jnp.int32(tok0),
            jnp.int32(req.max_new_tokens - 1))

    # ------------------------------------------------------------- staging --
    def _update_staging(self) -> None:
        """Chunk-boundary residency update: pin the chunk's write/dense-
        read set (fetching absent blocks synchronously), then prefetch
        predicted winner blocks into whatever staging capacity remains,
        writing evicted blocks back to the host pool first."""
        sm = self.staging
        sm.unpin_all()
        pos = np.asarray(self._state.regions.pos)
        enc = np.asarray(self._state.regions.enc_end)
        fpos = (None if self._state.fill_pos is None
                else np.asarray(self._state.fill_pos))
        flen = (None if self._state.fill_len is None
                else np.asarray(self._state.fill_len))
        bs = self.block_size
        W = CC.window_size(self.cfg.pariskv)
        sink = self.cfg.pariskv.sink_size
        P = self.prefill_budget
        required: List[tuple] = []        # (host_block, slot), pin order
        seen: set = set()

        def want(slot, lo_blk, hi_blk):
            row = self._bt[slot]
            for lb in range(max(0, lo_blk), min(self.nblk, hi_blk)):
                hb = int(row[lb])
                if hb >= 0 and hb not in seen:
                    seen.add(hb)
                    required.append((hb, slot))

        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            filling = (flen is not None and flen[slot] > 0
                       and fpos[slot] < flen[slot])
            if filling:
                # fill writes [start, start + chunk·P); the window of the
                # wherever-it-lands frontier (and post-completion decode
                # appends) stays inside [start - W, start + chunk·(P+1))
                start = int(fpos[slot])
                lo = max(0, start - W)
                hi = start + self.chunk_size * (max(P, 1) + 1)
            else:
                # decode appends [pos+1, pos+1+chunk); window + promotion
                # reads reach down to min(enc_end, pos+1-W)
                p1 = int(pos[slot]) + 1
                lo = max(0, min(int(enc[slot]), p1 - W))
                hi = p1 + self.chunk_size
            if sink > 0:
                want(slot, 0, -(-sink // bs))
            want(slot, lo // bs, -(-(hi + 1) // bs))

        writebacks: List[tuple] = []      # (evicted host block, staging slot)
        installs: List[tuple] = []        # (host block, staging slot)

        if self.faults is not None and self.faults.should("staging.storm"):
            # injected eviction storm: flush every resident staging block
            # (worst-case cold start). Evicted data rides the normal
            # write-back list — processed before any install reads the
            # host pool — so parity holds; only stall/bytes move. The
            # required set below re-stages what the chunk needs.
            for s in range(self.num_device_blocks):
                hb = int(sm.owner[s])
                if hb < 0:
                    continue
                writebacks.append((hb, s))
                sm.dev_map[hb] = -1
                sm.owner[s] = -1
                sm.pinned[s] = False
                sm.ref[s] = False
                sm.free.append(s)
                self.storm_evictions += 1

        def acquire_for(hb):
            got = sm.acquire()
            if got is None:
                return None
            s, ev = got
            if ev >= 0:
                writebacks.append((ev, s))
            sm.install(hb, s)
            installs.append((hb, s))
            return s

        for hb, slot in required:
            if sm.resident(hb):
                sm.pin(hb)
                continue
            s = acquire_for(hb)
            if s is None:
                raise RuntimeError(
                    f"staging pool exhausted while pinning the chunk's "
                    f"write/dense-read set (num_device_blocks="
                    f"{self.num_device_blocks}); grow the staging pool or "
                    f"shrink max_batch/chunk_size/prefill_budget")
            sm.pinned[s] = True

        self._last_prefetch = []
        if self.prefetch:
            owner = {b: sl for sl, blks in self._alloc.items()
                     for b in blks}
            k = max(1, self.num_device_blocks // 4)
            if self.prefetch_hook is not None:
                cand = list(self.prefetch_hook(self._touched_last.copy(), k))
            else:
                order = np.argsort(-self._touched_last, kind="stable")
                cand = [int(hb) for hb in order[:k]
                        if self._touched_last[hb] > 0]
            wanted = []
            for hb in cand:
                hb = int(hb)
                if (not 0 <= hb < self.num_blocks or hb in seen
                        or sm.resident(hb) or hb not in owner):
                    continue
                wanted.append(hb)
            # whole-batch slot grab (ISSUE 9): one acquire_batch call,
            # then installs — may come back short when slots are pinned
            got = sm.acquire_batch(len(wanted))
            for hb, (s, ev) in zip(wanted, got):
                if ev >= 0:
                    writebacks.append((ev, s))
                sm.install(hb, s)
                installs.append((hb, s))
                self._last_prefetch.append(hb)
                owner_req = self._slots[owner[hb]]
                if owner_req is not None:
                    owner_req.prefetched_blocks += 1

        if writebacks:
            evs = np.asarray([e for e, _ in writebacks], np.int64)
            ss = [s for _, s in writebacks]
            m = _bucket(len(ss))
            spad = np.zeros((m,), np.int32)
            spad[:len(ss)] = ss
            data = self._read_staging_fn(self._state, jnp.asarray(spad))
            for _, _, name in self._entries:
                k, v = data[name]
                self.host.writeback(name, evs, np.asarray(k)[:, :len(ss)],
                                    np.asarray(v)[:, :len(ss)])

        if installs:
            ss = [s for _, s in installs]
            m = _bucket(len(ss))
            spad = np.full((m,), self.num_device_blocks, np.int32)
            spad[:len(ss)] = ss
            hpad = np.zeros((m,), np.int64)
            hpad[:len(installs)] = [h for h, _ in installs]
            payloads = {}
            for _, _, name in self._entries:
                k, v = self.host.read_blocks(name, hpad)
                payloads[name] = (jnp.asarray(k), jnp.asarray(v))
            self._state = self._stage_fn(self._state, jnp.asarray(spad),
                                         payloads)

    def _harvest_fetch_stats(self) -> None:
        """Read the chunk's fetch-stat leaves back: per-request staging
        hit/miss/bytes counters, fetch-stall/callback attribution,
        prefetch-hit accounting, and the exponential-decay touch scores
        that seed the next chunk's prefetch prediction."""
        touched = np.zeros((self.num_blocks,), np.int64)
        rows = np.zeros((self.max_batch, 4), np.int64)
        miss_b = np.zeros((self.max_batch,), np.int64)
        degraded = np.zeros((self.max_batch,), np.int64)
        stall = 0.0
        calls = retries = timeouts = 0
        for si, ln, name in self._entries:
            f = self._state.caches[si][ln]["fetch"]
            touched += np.asarray(f["touched"]).sum(axis=0)
            r = np.asarray(f["rows"]).sum(axis=0).astype(np.int64)
            rows += r
            miss_b += (r[:, 2] * self.host.bytes_per_head_row(name)
                       + r[:, 3] * self.host.bytes_per_row(name))
            stall += float(np.asarray(f["stall"]).sum())
            calls += int(np.asarray(f["calls"]).sum())
            retries += int(np.asarray(f["retries"]).sum())
            timeouts += int(np.asarray(f["timeouts"]).sum())
            degraded += np.asarray(f["degraded"]).sum(axis=0)
        self.fetch_stall_chunks.append((stall, calls))
        self.fetch_stall_s += stall
        self.fetch_callbacks += calls
        self.fetch_retries += retries
        self.fetch_timeouts += timeouts
        self.degraded_steps += int(degraded.sum())
        # unique (post-dedup) traffic comes off the host counters — all
        # pariskv entries share (G, hd, dtype), so the first entry's
        # per-row byte sizes price the global unique-row deltas
        name0 = self._entries[0][2]
        uniq_b = ((self.host.fetched_unique_head_rows - self._uniq_head)
                  * self.host.bytes_per_head_row(name0)
                  + (self.host.fetched_unique_fill_rows - self._uniq_fill)
                  * self.host.bytes_per_row(name0))
        self._uniq_head = self.host.fetched_unique_head_rows
        self._uniq_fill = self.host.fetched_unique_fill_rows
        # stall/callbacks/unique-bytes are chunk-global: attribute per
        # request ∝ its share of fetch rows, even split when none fetched
        active = [s for s, rq in enumerate(self._slots) if rq is not None]
        fetch_rows = rows[:, 2] + rows[:, 3]
        tot = int(fetch_rows.sum())
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            req.staging_hits += int(rows[slot, 1])
            req.staging_misses += int(rows[slot, 2])
            req.fetched_bytes += int(miss_b[slot])
            share = (fetch_rows[slot] / tot if tot
                     else 1.0 / max(len(active), 1))
            req.fetched_unique_bytes += int(round(uniq_b * share))
            req.fetch_stall_s += stall * share
            req.fetch_callbacks += int(round(calls * share))
            req.fetch_retries += int(round(retries * share))
            req.fetch_timeouts += int(round(timeouts * share))
            req.degraded_steps += int(degraded[slot])
        owner = {b: sl for sl, blks in self._alloc.items() for b in blks}
        for hb in self._last_prefetch:
            if touched[hb] > 0:
                sl = owner.get(hb)
                if sl is not None and self._slots[sl] is not None:
                    self._slots[sl].prefetch_hits += 1
        self.staging.touch(np.flatnonzero(touched > 0))
        self._touched_last = (self._touch_decay * self._touched_last
                              + touched)

    # ------------------------------------------- loop phases (overrides) ----
    def _init_state(self) -> SV.SlotState:
        return SV.init_paged_slot_state(
            self.cfg, self.max_batch, self.num_blocks, self.block_size,
            self.n_max, prefill_budget=self.prefill_budget,
            num_device_blocks=self.num_device_blocks)

    def start(self) -> None:
        super().start()
        self.staging = offload_lib.StagingMap(self.num_blocks,
                                          self.num_device_blocks)
        for name in self.host.k:          # zero in place: the jitted
            self.host.k[name][:] = 0      # chunk holds this exact object
            self.host.v[name][:] = 0
        self.host.reset_counters()
        if self.pipeline is not None:     # same in-place contract: the
            self.pipeline.reset()         # chunk closes over the pipeline
        self._touched_last = np.zeros((self.num_blocks,), np.float64)
        self._last_prefetch = []
        self.fetch_stall_chunks = []
        self.fetch_stall_s = 0.0
        self.fetch_callbacks = 0
        self.fetch_retries = 0
        self.fetch_timeouts = 0
        self.degraded_steps = 0
        self.storm_evictions = 0
        self._uniq_head = 0
        self._uniq_fill = 0

    def _pre_chunk(self) -> None:
        super()._pre_chunk()              # lazy block allocation first
        self._update_staging()

    def chunk_args(self) -> tuple:
        return super().chunk_args() + (jnp.asarray(self.staging.dev_map),)

    def _collect(self, tokens: np.ndarray, rem_after: np.ndarray) -> None:
        self._harvest_fetch_stats()
        super()._collect(tokens, rem_after)

    def _reclaim_slot(self, slot: int) -> None:
        """Reclaim both tiers — refcount-aware across them (ISSUE 7):
        only blocks whose last holder just left have their staging slots
        freed (no write-back — the data is dead), host copies zeroed, and
        device metadata cleared. A still-shared block keeps all three:
        its staging residency stays valid for the surviving holders (and
        writes back through the normal recycle path — the block is
        immutable, so the copy stays final), its host bytes are live, and
        its device metadata feeds their retrieval."""
        dead = self._decref_blocks(slot)
        hbs = np.asarray(dead, np.int64)
        freed = (self.staging.release_host_blocks(hbs) if hbs.size else [])
        m = _bucket(max(len(freed), 1))
        spad = np.full((m,), self.num_device_blocks, np.int32)
        spad[:len(freed)] = freed
        self._state = self._evict_fn(self._state, self._dead_row(dead),
                                     jnp.asarray(spad), jnp.int32(slot))
        if hbs.size:
            self.host.zero_blocks(hbs)
        self._release_host(slot, dead=dead)

    def _evict_device(self, slot: int) -> None:
        self._state = self._cancel_fn(self._state, jnp.int32(slot))
        self._reclaim_slot(slot)

    def _release_slot(self, slot: int) -> None:
        self._reclaim_slot(slot)

    def _abort_admit(self, slot: int) -> None:
        # quarantine can interrupt _install_solo after write_prefill: the
        # dead blocks' host copies (and any staging residency) must not
        # leak into the next tenant of those blocks
        dead = self._decref_blocks(slot)
        hbs = np.asarray(dead, np.int64)
        if hbs.size:
            self.staging.release_host_blocks(hbs)
            self.host.zero_blocks(hbs)
        self._release_host(slot, dead=dead)

    def verify_invariants(self, check_hist: bool = True) -> None:
        """The paged audit plus the offload tiers: staging-map residency
        must mirror ownership (``dev_map[hb] == s ⟺ owner[s] == hb``),
        free staging slots must be unique and unowned, every resident
        host block must still be allocated to some request, and — at a
        chunk boundary — the fetch pipeline must hold no open tickets."""
        super().verify_invariants(check_hist=check_hist)
        sm = self.staging
        for hb in np.flatnonzero(sm.dev_map >= 0):
            s = int(sm.dev_map[hb])
            self._check(int(sm.owner[s]) == int(hb),
                        f"staging slot {s}: owner {int(sm.owner[s])} != "
                        f"dev_map inverse {int(hb)}")
            self._check(int(hb) in self._refcnt,
                        f"host block {int(hb)} resident in staging but "
                        f"not allocated to any slot")
        for s in np.flatnonzero(sm.owner >= 0):
            hb = int(sm.owner[s])
            self._check(int(sm.dev_map[hb]) == int(s),
                        f"host block {hb}: dev_map {int(sm.dev_map[hb])} "
                        f"!= owning staging slot {int(s)}")
        free = list(sm.free)
        self._check(len(set(free)) == len(free),
                    "staging free list holds duplicate slots")
        for s in free:
            self._check(int(sm.owner[s]) < 0,
                        f"staging slot {s} free but owned by block "
                        f"{int(sm.owner[s])}")
        self._check(len(free) + sm.resident_count()
                    == self.num_device_blocks,
                    "staging accounting leak: free + resident != "
                    f"{self.num_device_blocks}")
        if self.pipeline is not None:
            self._check(not self.pipeline._tickets,
                        "fetch pipeline holds open tickets at a chunk "
                        "boundary")

    def close(self) -> None:
        """Deterministic teardown: drain + join the fetch pipeline's
        worker executor and the host pool's guard executor so no
        non-daemon thread outlives the engine. Idempotent."""
        if self.pipeline is not None:
            self.pipeline.shutdown()
        self.host.close()

    def run(self) -> List[Request]:
        done = super().run()              # asserts the block allocator
        assert self.staging.resident_count() == 0, \
            "staging leak: residency map retained blocks after run"
        return done


class WaveServingEngine:
    """Legacy lockstep wave scheduler (baseline for benchmarks).

    All requests of a wave are prefilled as one right-aligned padded batch
    and decoded together to the wave's max generation length; new requests
    only join at wave boundaries. Timing is **wave-level**: every request
    of a wave reports the shared batched-prefill latency as ttft_s and the
    shared decode wall time as decode_s (the slot engine reports honest
    per-request numbers instead).
    """

    def __init__(self, cfg: ModelConfig, params, n_max: int = 4096,
                 max_batch: int = 8, greedy: bool = True, use_pariskv=True):
        self.cfg = cfg
        self.params = params
        self.n_max = n_max
        self.max_batch = max_batch
        self.greedy = greedy
        self.use_pariskv = use_pariskv
        self._prefill = jax.jit(
            lambda p, t, m: SV.prefill(p, cfg, t, n_max, m))
        self._decode = jax.jit(
            lambda p, tok, st: SV.decode_step(p, cfg, tok, st,
                                              use_pariskv=use_pariskv))
        self.queue: List[Request] = []
        self.peak_concurrency = 0   # max requests decoding in one wave

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _pad_prompts(self, reqs: List[Request]):
        s = max(len(r.prompt) for r in reqs)
        s = max(s, 8)
        toks = np.zeros((len(reqs), s), np.int32)
        for i, r in enumerate(reqs):
            toks[i, s - len(r.prompt):] = r.prompt   # right-align
        return jnp.asarray(toks)

    def run(self) -> List[Request]:
        done: List[Request] = []
        while self.queue:
            wave = self.queue[:self.max_batch]
            self.queue = self.queue[self.max_batch:]
            done.extend(self._run_wave(wave))
        return done

    def _run_wave(self, wave: List[Request]) -> List[Request]:
        b = len(wave)
        self.peak_concurrency = max(self.peak_concurrency, b)
        toks = self._pad_prompts(wave)
        media = None
        if wave[0].media is not None:
            media = jnp.asarray(np.stack([r.media for r in wave]))
        t0 = time.perf_counter()
        logits, state = self._prefill(self.params, toks, media)
        logits.block_until_ready()
        t1 = time.perf_counter()
        for r in wave:
            r.ttft_s = t1 - t0

        max_new = max(r.max_new_tokens for r in wave)
        outs = np.zeros((b, max_new), np.int32)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for step in range(max_new):
            outs[:, step] = np.asarray(tok)
            logits, state = self._decode(self.params, tok, state)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(logits)
        t2 = time.perf_counter()
        for i, r in enumerate(wave):
            r.output = outs[i, :r.max_new_tokens]
            r.decode_s = (t2 - t1)
            r.token_times = [t1 + (j + 1) * (t2 - t1) / max_new
                             for j in range(len(r.output))]
        return wave
