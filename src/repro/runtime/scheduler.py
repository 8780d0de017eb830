"""Request schedulers over the serving engine.

Two serving cores share one request/stats vocabulary:

``WaveScheduler`` (baseline) — drain-and-restart: forms *waves* of up to
``batch_size`` requests with a shared (padded) prompt length, runs prefill
once and decodes every request to the wave's max ``max_new``.  One straggler
holds the whole wave and finished rows burn full decode FLOPs.

``ContinuousScheduler`` (slot engine) — a fixed-capacity batch of *slots*
with an admit → step → retire loop: decode runs with a per-slot position
vector, finished/empty slots are masked inside the jitted step, and new
requests are admitted **in-flight** by prefilling into free slots of the
live cache — no batch restart, no recompile (prompt lengths bucket to powers
of two).  This closes the batch-utilization gap that arXiv 2407.07304 / the
LIMINAL analysis identify as the dominant decode-throughput lever once
per-token sync cost is minimized.

**Chunked prefill** (``prefill_chunk``): EVERY prompt on an arch whose
capability record supports chunked admission streams through the engine's
fused mixed prefill/decode step — each serving step prefills one
fixed-width chunk per admitting slot AND decodes one token per active slot,
so a long prompt never stalls in-flight decode for more than one chunk of
compute (LIMINAL's point: inter-token latency, not aggregate throughput,
is the binding constraint once batching works), and a short prompt
completes in its first chunk.  The chunked path uses one fixed chunk
shape, so admission compiles exactly once; the pow-2 bucketed single-shot
prefill survives only as the fallback for families whose capability record
blocks chunked admission (recurrent state, modality-prefix frontends,
multi-codebook heads — see ``core/capabilities.py``) or when chunking is
explicitly disabled.  Greedy outputs are bit-identical either way.

Arrivals are measured on a virtual clock of *decode steps* so schedules are
deterministic and testable: a request with ``arrival_step=s`` becomes
admissible once ``s`` decode steps have executed.  ``WaveScheduler`` ignores
arrivals (it drains whatever is queued) — it is the pessimistic baseline.

**Priority classes & overload.**  Every request carries a priority class —
``interactive`` | ``standard`` | ``batch`` — with an optional per-class
per-token SLO target.  Admission orders arrivals by class (stable within a
class, so FIFO and preemption's requeue-at-head survive), a configurable
slot/block quota can be held back for ``interactive``, preemption evicts
the lowest-class-youngest victim, and an optional degradation controller
(``runtime/overload.py``) sheds ``batch`` / suspends spec decode / tightens
the admission window under sustained overload, restoring in reverse with
hysteresis.  Every lever changes *which* requests run and *when* — never
their tokens: admitted survivors' greedy streams stay bit-identical to an
unloaded run.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.kernels import decode_attention
from repro.models.common import pad_to
from repro.runtime import kvcache, tracing
from repro.runtime.engine import Engine
from repro.runtime.faults import (FaultPlan, MigrationFault,
                                  TransientStepError)


def percentile_summary(vals) -> Optional[Dict[str, float]]:
    """The one percentile helper every latency summary uses: linear-
    interpolated percentiles (np.percentile) so p50 is the true median —
    not the upper-median ``vals[n//2]`` shortcut, which disagrees with the
    interpolated p95 two keys later on every even-sized sample.  Returns
    None for an empty sample; a single sample is its own mean/p50/p95/max."""
    v = np.asarray(list(vals), np.float64)
    if v.size == 0:
        return None
    return {
        "mean": float(v.mean()),
        "p50": float(np.percentile(v, 50)),
        "p95": float(np.percentile(v, 95)),
        "max": float(v.max()),
    }


def _tok_scalar(tok) -> int:
    """The token id used for EOS / vocab-range checks: the token itself for
    single-codebook archs, codebook 0 of the frame for multi-codebook ones
    (codebook 0 carries the primary/EOS stream in every config here)."""
    a = np.asarray(tok)
    return int(a if a.ndim == 0 else a.reshape(-1)[0])


# Priority classes, best first.  Rank 0 (interactive) admits first, is
# never shed by the degradation ladder, and is protected by the reserve
# quotas; rank 2 (batch) is shed first and preempted first.
PRIORITY_CLASSES = ("interactive", "standard", "batch")
PRIORITY_RANK = {c: i for i, c in enumerate(PRIORITY_CLASSES)}

# engine programs with a decode half (their dispatch spans carry per-slot
# context and budget)
_DECODING = ("decode", "mixed", "spec")


def _check_priority(priority: str) -> str:
    if priority not in PRIORITY_RANK:
        raise ValueError(
            f"unknown priority class {priority!r}; expected one of "
            f"{PRIORITY_CLASSES}")
    return priority


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (prompt_len,) or (prompt_len, ncb)
    max_new: int
    eos_id: Optional[int] = None
    arrival_step: int = 0         # virtual-clock arrival (decode steps)
    submitted_at: float = field(default_factory=time.monotonic)
    output: Optional[np.ndarray] = None
    # per-request counters, times in seconds on time.monotonic: queue_s
    # (submitted_at to slot admission), prefill_s (slot admission to the
    # first emitted token), ttft_s (submitted_at to the first emitted
    # token); the frontend adds inbox_s (its inbox to submitted_at)
    stats: Dict = field(default_factory=dict)
    # why the request retired: "stop" (EOS) | "length" (budget) | "error"
    # (quarantined: poisoned output, persistent step failure, failed
    # handoff, pool exhaustion, livelock abort) | "timeout" (deadline)
    finish_reason: Optional[str] = None
    # wall-clock deadline in seconds from submission; the scheduler retires
    # the request with finish_reason "timeout" (keeping tokens emitted so
    # far) once it expires — queued, mid-prefill, or mid-decode alike
    deadline_s: Optional[float] = None
    # priority class (PRIORITY_CLASSES): drives admission order, the
    # interactive reserve quotas, preemption victim choice, and which
    # requests the degradation ladder sheds.  "shed" joins the
    # finish_reason vocabulary: retired at admission under overload,
    # empty output, never held a slot.
    priority: str = "standard"


class WaveScheduler:
    def __init__(self, engine: Engine, batch_size: int, pad_id: int = 0):
        self.engine = engine
        self.batch_size = batch_size
        self.pad_id = pad_id
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self._next_id = 0

    def submit(self, prompt: np.ndarray, max_new: int,
               eos_id: Optional[int] = None, arrival_step: int = 0,
               priority: str = "standard") -> int:
        rid = self._next_id
        self._next_id += 1
        # wave mode records the class for reporting but schedules blind:
        # it is the pessimistic baseline on purpose
        self.queue.append(Request(rid, np.asarray(prompt), max_new, eos_id,
                                  arrival_step,
                                  priority=_check_priority(priority)))
        return rid

    def _form_wave(self) -> List[Request]:
        wave = self.queue[: self.batch_size]
        self.queue = self.queue[self.batch_size:]
        return wave

    def run(self) -> List[Request]:
        """Drain the queue; returns completed requests in completion order."""
        while self.queue:
            wave = self._form_wave()
            self._run_wave(wave)
        return self.done

    def _run_wave(self, wave: List[Request]) -> None:
        # honest tail sizing: a partial last wave only pays for the rows it
        # needs, padded up to data-parallel divisibility (generate shards the
        # batch over dp), not up to the full configured batch_size
        dp_total = self.engine.ctx.dist.dp * self.engine.ctx.dist.pods
        b = pad_to(max(len(wave), 1), dp_total)
        plen = max(len(r.prompt) for r in wave)
        max_new = max(r.max_new for r in wave)
        ncb = self.engine.cfg.n_codebooks
        shape = (b, plen) if ncb == 1 else (b, plen, ncb)
        prompts = np.full(shape, self.pad_id, dtype=np.int32)
        for i, r in enumerate(wave):
            # left-align; short prompts are right-padded (positions aligned)
            prompts[i, : len(r.prompt)] = r.prompt
        t0 = time.monotonic()
        out = self.engine.generate(prompts, max_new)       # (b, max_new[, ncb])
        dt = time.monotonic() - t0
        cut = []
        for i, r in enumerate(wave):
            toks = out[i, : r.max_new]
            if r.eos_id is not None:
                flat = toks if toks.ndim == 1 else toks[..., 0]
                hits = np.nonzero(flat == r.eos_id)[0]
                if hits.size:
                    toks = toks[: hits[0] + 1]
            cut.append(toks)
        # throughput from tokens actually delivered: EOS-cut, per-request
        # max_new — NOT the padded wave_b * wave_max_new the step loop ran
        emitted = sum(len(t) for t in cut)
        for r, toks in zip(wave, cut):
            r.output = toks
            flat = toks if toks.ndim == 1 else toks[..., 0]
            r.finish_reason = ("stop" if (r.eos_id is not None and len(flat)
                                          and flat[-1] == r.eos_id)
                               else "length")
            r.stats = {
                "wave_batch": len(wave),
                "queue_s": t0 - r.submitted_at,
                "wave_s": dt,
                "emitted": len(toks),
                "tok_per_s": emitted / dt if dt > 0 else float("inf"),
            }
            self.done.append(r)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


@dataclass
class _Pending:
    """One dispatched-but-unlanded fused decode block (overlapped loop).

    The token/state outputs stay DEVICE FUTURES until ``_land_next``
    materializes them one step late; the captured host context is what the
    landing replay needs to attribute emissions exactly as the blocking
    loop would have: the slot objects live at dispatch, the per-slot eos
    ids, and the predicted-active mask (a superset of the device's true
    active set — EOS surprises freeze slots earlier than prediction)."""

    toks: object                  # device (n, B) sampled tokens
    pos: object                   # device (B,) post-block positions
    done: object                  # device (B,) post-block done mask
    remaining: object             # device (B,) post-block budgets
    n: int                        # fused steps in this block
    base_step: int                # engine step index of the block's first row
    slots: List                   # slot objects at dispatch (replay targets)
    eos: np.ndarray               # per-slot eos ids at dispatch
    active: np.ndarray            # predicted-active mask at dispatch
    adm_mark: bool                # _admission_mark consumed by this block
    itl_anchor: Optional[float]   # dispatch-time ITL anchor (disagg) or None
    dispatch_t: float = 0.0       # host clock right after dispatch returned


@dataclass
class _Slot:
    req: Optional[Request] = None
    toks: List = field(default_factory=list)
    admitted_step: int = 0
    # chunked admission in progress: absolute offset of the next prefill
    # chunk (None = not chunking), and whether the opening chunk already ran
    # (slot state resets exactly once, on the first chunk)
    chunk_next: Optional[int] = None
    chunk_started: bool = False
    # spec-decode drafting history: preallocated prompt+generated buffer so
    # every verify step appends O(new tokens) instead of re-concatenating
    # the whole history (O(len) per step = quadratic per request)
    hist: Optional[np.ndarray] = None
    hist_len: int = 0


class ContinuousScheduler:
    """Slot-based continuous batching over ``Engine``'s slot programs.

    The loop per iteration: retire finished slots, admit arrived requests
    into free slots (one bucketed in-flight prefill), then run a fused block
    of up to ``block_steps`` masked decode steps.  Per-request streaming is
    available via ``on_token(rid, token)``.
    """

    def __init__(self, engine: Engine, n_slots: int, pad_id: int = 0,
                 block_steps: int = 8, min_bucket: int = 8,
                 responsive_blocks: bool = False,
                 on_token: Optional[Callable[[int, int], None]] = None,
                 prefill_chunk: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 spec_ngram: Optional[int] = None,
                 overlap: Optional[bool] = None,
                 fault_plan: Optional[str] = None,
                 max_step_retries: Optional[int] = None,
                 retry_backoff_s: Optional[float] = None,
                 slo_targets: Optional[Dict[str, float]] = None,
                 reserve_slots: Optional[int] = None,
                 reserve_blocks: Optional[int] = None,
                 overload_opts: Optional[Dict] = None):
        self.engine = engine
        self.B = n_slots
        self.pad_id = pad_id
        self.block_steps = block_steps
        self.min_bucket = min_bucket
        self.responsive_blocks = responsive_blocks
        self.on_token = on_token
        cfg = engine.cfg
        caps = engine.caps
        # multi-codebook archs decode (n_slots, ncb) token frames; codebook
        # 0 carries the EOS/primary stream (see _tok_scalar)
        self.ncb = cfg.n_codebooks
        # modality-prefix archs prepend a fixed encoder prefix: every cache
        # extent / position is offset by it (the engine's slot prefill
        # synthesizes the stub features itself)
        self._prefix = cfg.frontend.prefix_len if cfg.frontend else 0
        # The capability record's max_prompt caps prompts and buckets: a
        # sliding-window (local_attn) ring cache keeps only the LAST window
        # tokens, so padding a bucketed whole-prompt admission past the
        # window would push real prompt history out of the ring.
        self.prompt_limit = engine.max_len
        if caps.max_prompt is not None:
            self.prompt_limit = min(self.prompt_limit, caps.max_prompt)
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self._next_id = 0
        self._rng = jax.random.key(engine.seed + 17)
        self._calls = 0
        self.caches = None
        self.slots = [_Slot() for _ in range(n_slots)]
        self.step_count = 0               # virtual clock: decode steps so far
        self.tok = np.zeros((n_slots,) if self.ncb == 1
                            else (n_slots, self.ncb), np.int32)
        self.pos = np.zeros((n_slots,), np.int32)
        self.dones = np.ones((n_slots,), bool)
        self.remaining = np.zeros((n_slots,), np.int32)
        self.eos = np.full((n_slots,), -1, np.int32)
        self.stats = {
            "decode_steps": 0, "slot_steps": 0, "active_slot_steps": 0,
            "emitted": 0, "admission_rounds": 0, "in_flight_admissions": 0,
            "prefill_tokens": 0,
            "prefill_chunks": 0, "chunked_admissions": 0,
            # host/device timing split (both loops): host_blocked_s sums the
            # np.asarray waits (every materialization routes through
            # _materialize), host_overlap_s sums host time spent between a
            # dispatch and its landing — the work the overlapped loop takes
            # off the device critical path
            "host_blocked_s": 0.0, "host_overlap_s": 0.0, "landings": 0,
            "eos_rollbacks": 0, "dispatch_ahead_steps": 0,
            "max_dispatch_ahead": 0, "shed_requests": 0,
            # failure-isolation counters (all loud: request_summary surfaces
            # them whenever any is nonzero)
            "step_faults": 0, "step_retries": 0, "quarantined": 0,
            "timeouts": 0, "aborts_exhaustion": 0, "livelock_aborts": 0,
            "migration_faults": 0,
        }
        # fault tolerance: the injection/watchdog plan (empty spec = every
        # hook compiles to a no-op), bounded retry policy for transient
        # step failures, and the liveness clock the frontend watchdog reads
        par = engine.parallel
        self.faults = FaultPlan.parse(
            fault_plan if fault_plan is not None else par.fault_plan)
        self.max_step_retries = int(
            max_step_retries if max_step_retries is not None
            else par.max_step_retries)
        self.retry_backoff_s = float(
            retry_backoff_s if retry_backoff_s is not None
            else par.retry_backoff_s)
        self._retry_streak = 0            # consecutive failures, same step
        self.vocab = engine.cfg.vocab_size
        self._progress_t = time.monotonic()
        self._has_deadlines = False       # any live request carries one
        # slots force-retired (quarantine/timeout) that the DEVICE still
        # believes are active: landed device done-masks are OR-ed with this
        # so in-flight blocks dispatched before the retirement cannot
        # resurrect the slot; cleared when the slot is reassigned
        self._forced_done = np.zeros((n_slots,), bool)
        if self.faults:
            engine.dispatch_hook = self._fault_dispatch
        # overlapped host/device loop: dispatch block N+1 on block N's
        # device-future outputs, land (np.asarray) one block late.  Host
        # decisions between dispatch and landing run on a PREDICTED state:
        # budget decrements are deterministic, so prediction is exact except
        # when a landed token turns out to be EOS — fixed by a one-step
        # rollback at landing (_land_next).  Greedy streams are
        # bit-identical to the blocking loop: overlap reorders host
        # observation, never device math.
        self.overlap = bool(engine.parallel.overlap_decode
                            if overlap is None else overlap)
        from collections import deque as _dq
        self._pipeline: "_dq[_Pending]" = _dq()
        # exact landed frontier (rolling pre-state for the landing replay)
        self._exact_tok = self._exact_pos = None
        self._exact_dones = self._exact_rem = None
        self._stamp_itl_at_dispatch = False   # disagg overrides (see its doc)
        # frontend hook: called with each Request as it retires
        self.on_finish: Optional[Callable[[Request], None]] = None
        # chunked prefill: EVERY prompt on a chunk-capable arch streams
        # through the fused mixed prefill/decode step — long ones
        # chunk-by-chunk (admission never stalls in-flight decode for more
        # than one chunk of compute), short ones in a single chunk.  One
        # fixed chunk shape = one compiled admission program; blocked
        # families fall back to the legacy bucketed single-shot prefill.
        # Gating is the capability record's: an inherited config default
        # falls back silently, an EXPLICIT constructor request raises the
        # registry's uniform error.
        chunk = (prefill_chunk if prefill_chunk is not None
                 else engine.parallel.prefill_chunk)
        if chunk and not caps.supports("chunked"):
            if prefill_chunk is not None:
                caps.require("chunked")
            chunk = 0
        self.chunk = min(int(chunk), self.prompt_limit) if chunk else 0
        # speculative decoding: an n-gram prompt-lookup drafter proposes
        # spec_k tokens per active slot; one fused verify step (a width
        # spec_k+1 chunk at the decode frontier) scores them all and emits
        # the accepted prefix + one bonus token.  Eligibility is the
        # capability record's ``spec`` path (the verify chunk resumes
        # mid-cache over the slot stripe), gated like chunked prefill:
        # config defaults fall back silently, explicit requests raise.
        sk = spec_k if spec_k is not None else engine.parallel.spec_k
        if sk and not caps.supports("spec"):
            if spec_k is not None:
                caps.require("spec")
            sk = 0
        self.spec_k = max(0, int(sk or 0))
        self.spec_ngram = int(spec_ngram if spec_ngram is not None
                              else engine.parallel.spec_ngram)
        self.drafter = None
        if self.spec_k:
            from repro.runtime.drafter import NgramDrafter
            self.drafter = NgramDrafter(self.spec_k,
                                        ngram_max=self.spec_ngram)
            self.stats.update({
                "spec_steps": 0, "spec_slot_steps": 0, "spec_proposed": 0,
                "spec_accepted": 0, "spec_emitted": 0,
            })
        # decode inter-token latency stream: (seconds/step, during-admission);
        # bounded so a long-lived server doesn't grow host memory per step —
        # summaries cover the most recent window
        from collections import deque
        self._itl: "deque[Tuple[float, bool]]" = deque(maxlen=65536)
        self._last_step_t: Optional[float] = None
        self._admission_mark = False
        # emitted tokens per (engine step, active slot): 1 for plain masked
        # decode, 1..spec_k+1 under speculative decoding
        self._tps: "deque[int]" = deque(maxlen=65536)
        # overload resilience: per-class per-token SLO targets, the
        # interactive reserve quotas (slots here; blocks read by the paged
        # backend), and the graceful-degradation controller.  Constructor
        # args override ParallelConfig; ``overload_opts`` merges over the
        # config-derived controller knobs (and its "enabled" key can turn
        # the controller on for a single scheduler on a shared engine).
        self.slo_targets = {"interactive": par.slo_interactive_s,
                            "standard": par.slo_standard_s,
                            "batch": par.slo_batch_s}
        if slo_targets:
            self.slo_targets.update(slo_targets)
        self.reserve_slots = int(par.interactive_reserve_slots
                                 if reserve_slots is None else reserve_slots)
        self.reserve_blocks = int(par.interactive_reserve_blocks
                                  if reserve_blocks is None
                                  else reserve_blocks)
        opts = {"enabled": par.overload_degrade,
                "queue_hi": par.overload_queue_hi,
                "queue_lo": par.overload_queue_lo,
                "slo_s": float(self.slo_targets.get("interactive") or 0.0),
                "itl_hi": par.overload_itl_hi,
                "itl_lo": par.overload_itl_lo,
                "patience": par.overload_patience,
                "cooldown": par.overload_cooldown}
        opts.update(overload_opts or {})
        self.overload_ctl = None
        if opts.pop("enabled"):
            if opts["queue_hi"] <= 0:
                opts["queue_hi"] = 2 * n_slots
            if opts["queue_lo"] <= 0:
                opts["queue_lo"] = max(1, n_slots // 2)
            opts["queue_lo"] = min(opts["queue_lo"], opts["queue_hi"])
            from repro.runtime.overload import OverloadController
            self.overload_ctl = OverloadController(**opts)
        self.stats["classes"] = {c: {"served": 0, "shed": 0, "timeout": 0,
                                     "error": 0} for c in PRIORITY_CLASSES}
        self.stats.update({"burst_injected": 0, "overload_transitions": 0,
                           "spec_off_rounds": 0})
        # recent landed per-step ITL window the controller reads (wall
        # clock — advisory next to the deterministic queue-depth signal)
        self._itl_recent: "deque[float]" = deque(
            maxlen=(self.overload_ctl.window if self.overload_ctl else 32))

    # -- submission -------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int,
               eos_id: Optional[int] = None, arrival_step: int = 0,
               deadline_s: Optional[float] = None,
               priority: str = "standard") -> int:
        prompt = np.asarray(prompt)
        if self._prefix + len(prompt) + max_new > self.engine.max_len:
            raise ValueError(
                f"request needs {self._prefix + len(prompt)}+{max_new} "
                f"positions > max_len {self.engine.max_len}")
        if len(prompt) > self.prompt_limit:
            raise ValueError(
                f"prompt len {len(prompt)} exceeds the sliding-window cache "
                f"({self.prompt_limit}); longer-than-window prompts are not "
                f"admissible in-flight yet — use WaveScheduler")
        if len(prompt) < 2:
            raise ValueError("prompts must have >= 2 tokens")
        rid = self._next_id
        self._next_id += 1
        self.queue.append(Request(rid, prompt, max_new, eos_id, arrival_step,
                                  deadline_s=deadline_s,
                                  priority=_check_priority(priority)))
        if deadline_s is not None:
            self._has_deadlines = True
        return rid

    # -- internals --------------------------------------------------------
    def _next_rng(self):
        self._calls += 1
        return jax.random.fold_in(self._rng, self._calls)

    def _dispatch(self, kind: str, call, n: int = 1, chunks=()):
        """Runs ``call``, one engine dispatch of ``n`` steps, inside its
        ``repro.sched.dispatch`` span.  The span's stats are the step's
        work: for the programs that decode, each decoding slot's context
        at step 0 and its token budget (from the host arrays), the prefill
        ``chunks`` as (start, length) pairs, the programs built during
        the call, and where the decode and mixed programs run the paged
        decode kernel, its steps per layer that copy and compute for those
        slots (``kv_steps``) out of the steps a walk of every slot's whole
        view would take (``kv_grid``)."""
        def live():
            if kind not in _DECODING:
                return np.zeros(0, np.int64), np.zeros(0, np.int64)
            a = ~self.dones & (self.remaining > 0)
            return (self.pos[a].astype(np.int64) + 1,
                    self.remaining[a].astype(np.int64))

        def live_tokens():
            ctx, rem = live()
            m = np.minimum(rem, n)
            return int((m * ctx + m * (m - 1) // 2).sum())

        def kv(stat):
            walk = self._kv_walk() if kind in ("decode", "mixed") else None
            return None if walk is None else stat(*walk)

        sp = tracing.span(
            "sched.dispatch", kind=kind, n=n, step=self.step_count,
            live_tokens=live_tokens,
            ctx=lambda: " ".join(map(str, live()[0])),
            rem=lambda: " ".join(map(str, live()[1])),
            chunks=lambda: " ".join(f"{a}:{c}" for a, c in chunks),
            kv_steps=lambda: kv(lambda tokens, n_s: int(
                (-(-live()[0] // tokens)).sum())),
            kv_grid=lambda: kv(lambda tokens, n_s: self.B * n_s))
        built = tracing.builds()
        with sp:
            out = call()
            sp.set_metadata(built=tracing.builds() - built)
        return out

    def _kv_walk(self) -> Optional[Tuple[int, int]]:
        """(tokens per step, steps per slot's whole view) of the paged
        decode kernel, where the decode programs run it; None here (dense
        caches)."""
        return None

    def _inflight_mask(self) -> Optional[np.ndarray]:
        """Slots with emissions still in flight (covered by an unlanded
        block's predicted-active mask) — they may not retire or be reused
        until their record lands."""
        if not self._pipeline:
            return None
        m = np.zeros((self.B,), bool)
        for rec in self._pipeline:
            m |= rec.active
        return m

    def _finish(self, r: Request) -> None:
        """The single retirement funnel: every path that moves a request to
        ``done`` (retire, quarantine, deadline expiry, admission shed,
        landing abort) routes here so the per-class counters and the
        frontend's ``on_finish`` hook can never drift apart.

        ``finished_step`` stamps retirement on the virtual decode-step
        clock — with arrival_step it gives a latency measure that is
        exactly reproducible run to run (the SLO bench compares scheduling
        policies on it, free of wall-clock noise)."""
        r.stats["finished_step"] = self.step_count
        buckets = self.stats["classes"].setdefault(
            r.priority, {"served": 0, "shed": 0, "timeout": 0, "error": 0})
        fr = r.finish_reason or "length"
        buckets["served" if fr in ("stop", "length")
                else fr if fr in ("shed", "timeout") else "error"] += 1
        self.done.append(r)
        if self.on_finish is not None:
            self.on_finish(r)

    def _shed_request(self, r: Request) -> None:
        """Admission-time load shed (degradation lever): the queued request
        retires immediately with finish_reason "shed" and an empty output —
        it never held a slot, so no stream or pool state is touched."""
        r.output = np.zeros((0,), np.int32)
        r.finish_reason = "shed"
        r.stats.update({"emitted": 0, "finished_at": time.monotonic()})
        self.stats["shed_requests"] += 1
        self._finish(r)

    def _retire(self) -> None:
        now = time.monotonic()
        infl = self._inflight_mask()
        for i, s in enumerate(self.slots):
            # mid-prefill slots ride with done=True (decode freezes them)
            # but are NOT finished — their chunks are still streaming in;
            # under overlap, a done slot with unlanded emissions waits for
            # its record to land (the tail tokens aren't host-visible yet)
            if (s.req is not None and self.dones[i] and s.chunk_next is None
                    and (infl is None or not infl[i])):
                r = s.req
                r.output = np.asarray(s.toks, dtype=np.int32)
                if r.finish_reason is None:
                    r.finish_reason = (
                        "stop" if (r.eos_id is not None and s.toks
                                   and _tok_scalar(s.toks[-1]) == r.eos_id)
                        else "length")
                r.stats.update({
                    "emitted": len(s.toks),
                    "finished_at": now,
                    "decode_steps_held": self.step_count - s.admitted_step,
                })
                self.slots[i] = _Slot()
                self._finish(r)

    def _bucket(self, plen: int) -> int:
        """Pow-2 prompt bucket — whole-prompt admission only (``self.chunk
        == 0``: the arch's capability record blocks chunked admission, or
        chunking is explicitly disabled).  Chunk-capable archs admit every
        prompt — short ones included — through the fixed-width mixed step,
        which compiles exactly once; each distinct bucket width here is a
        separate XLA compilation, the recompile cost this path is gated
        for."""
        assert self.chunk == 0, "bucketed admission is fallback-arch only"
        b = self.min_bucket
        while b < plen:
            b *= 2
        return min(b, self.prompt_limit)

    def _free_slots(self) -> List[int]:
        """Slots admission may fill (the disagg scheduler restricts this to
        the prefill pool; landings fill decode-pool slots directly)."""
        return [i for i, s in enumerate(self.slots) if s.req is None]

    def _admissible(self) -> List[Request]:
        """Arrived queue entries in class-aware order.  Classes the
        degradation ladder is shedding retire immediately (finish_reason
        "shed"); the rest sort STABLY by priority rank — interactive first
        — so FIFO order (and preemption's requeue-at-head) is preserved
        within each class."""
        arrived = [r for r in self.queue if r.arrival_step <= self.step_count]
        ctl = self.overload_ctl
        if ctl is not None and ctl.shed_classes and arrived:
            for r in [r for r in arrived if r.priority in ctl.shed_classes]:
                self.queue.remove(r)
                self._shed_request(r)
            arrived = [r for r in arrived
                       if r.priority not in ctl.shed_classes]
        arrived.sort(key=lambda r: PRIORITY_RANK[r.priority])
        return arrived

    def _admission_quota(self, n_free: int) -> int:
        """Admissions allowed this round: the free-slot count, tightened to
        the degradation ladder's cap on CONCURRENT admissions (counting
        slots still mid-chunk-prefill) at tight-admission."""
        ctl = self.overload_ctl
        if ctl is not None and ctl.admission_cap is not None:
            n_free = min(n_free,
                         max(0, ctl.admission_cap - len(self._prefilling())))
        return n_free

    def _admit(self) -> int:
        free = self._free_slots()
        arrived = self._admissible()
        if not free or not arrived:
            return 0
        # class-ordered selection; non-interactive requests may not eat
        # into the interactive slot reserve.  Arrivals are class-sorted, so
        # the first refusal ends the scan (everything after is the same
        # class or lower — no reordering under pressure beyond class rank).
        quota = self._admission_quota(len(free))
        chosen: List[Request] = []
        left = len(free)
        for r in arrived:
            if len(chosen) >= quota:
                break
            if r.priority != "interactive" and left <= self.reserve_slots:
                break
            chosen.append(r)
            left -= 1
        if not chosen:
            return 0
        for r in chosen:
            self.queue.remove(r)
        in_flight = any(s.req is not None
                        and (not self.dones[i] or s.chunk_next is not None)
                        for i, s in enumerate(self.slots))
        now = time.monotonic()
        short = []
        for slot, r in zip(free, chosen):
            self.slots[slot] = _Slot(req=r, admitted_step=self.step_count)
            self._forced_done[slot] = False
            r.stats["queue_s"] = now - r.submitted_at
            r.stats["admitted_step"] = self.step_count
            if self.chunk:
                # ALL chunk-eligible prompts stream through the fused mixed
                # step: long prompts chunk-by-chunk (decode never waits for
                # the whole prompt), short prompts in a single chunk — the
                # mixed program's width is fixed, so admission compiles
                # exactly once, with no pow-2 prompt buckets at all
                self.slots[slot].chunk_next = 0
                self.dones[slot] = True
                self.remaining[slot] = 0
                if len(r.prompt) > self.chunk:
                    self.stats["chunked_admissions"] += 1
            else:
                short.append((slot, r))
        self.stats["admission_rounds"] += 1
        if in_flight:
            self.stats["in_flight_admissions"] += len(chosen)
        if short:
            self._prefill_short(short)
        return len(chosen)

    def _prefill_short(self, pairs) -> None:
        """Legacy single-shot admission for prompts within the chunk budget
        (and for fallback archs): one bucketed full-width prefill."""
        Lp = self._bucket(max(len(r.prompt) for _, r in pairs))
        shape = (self.B, Lp) if self.ncb == 1 else (self.B, Lp, self.ncb)
        tokens = np.full(shape, self.pad_id, np.int32)
        admit = np.zeros((self.B,), bool)
        plens = np.ones((self.B,), np.int32)
        for slot, r in pairs:
            tokens[slot, : len(r.prompt)] = r.prompt
            admit[slot] = True
            plens[slot] = len(r.prompt)
        new_tok, self.caches = self._dispatch(
            "prefill", lambda: self.engine.prefill_into_slots(
                self.caches, tokens, admit, plens, self._next_rng()),
            chunks=[(0, len(r.prompt)) for _, r in pairs])
        self.stats["prefill_tokens"] += int(plens[admit].sum())
        self._admission_mark = True
        self._finish_admission([s for s, _ in pairs], [r for _, r in pairs],
                               admit, np.array(new_tok))

    def _finish_admission(self, free, chosen, admit, new_tok) -> None:
        """Shared post-prefill host bookkeeping (dense, paged, chunked):
        record each finishing request's first emitted token and arm its
        decode state.  ``ttft_s`` is stamped HERE — under chunked admission
        that is the step whose chunk completed the prompt, so TTFT reflects
        the first token actually *emitted*, not slot assignment."""
        with tracing.span("sched.emit"):
            adm = admit if new_tok.ndim == 1 else admit[:, None]
            self.tok = np.where(adm, new_tok, self.tok)
            for slot, r in zip(free, chosen):
                t = _tok_scalar(new_tok[slot])
                if not 0 <= t < self.vocab:
                    # poisoned prefill output (the int32 image of
                    # non-finite logits): quarantine before the garbage id
                    # reaches the stream — the first decode dispatch masks
                    # the slot out
                    self._quarantine_slot(
                        slot, "error", f"poisoned prefill token {t}")
                    continue
                self.slots[slot].toks.append(
                    t if self.ncb == 1
                    else np.asarray(new_tok[slot], np.int32).copy())
                if self.on_token is not None:
                    self.on_token(r.rid, t)
                self.pos[slot] = len(r.prompt) + self._prefix
                self.remaining[slot] = r.max_new - 1
                self.eos[slot] = -1 if r.eos_id is None else r.eos_id
                self.dones[slot] = (r.max_new <= 1) or (
                    r.eos_id is not None and t == r.eos_id)
                r.stats["ttft_s"] = time.monotonic() - r.submitted_at
                r.stats["prefill_s"] = r.stats["ttft_s"] - r.stats["queue_s"]
                self.stats["emitted"] += 1

    def _decode_inputs(self):
        """Decode-state inputs for the next engine dispatch: the newest
        unlanded block's device-future outputs when the pipeline is
        non-empty (exact by construction — the device chains its own
        masking), host arrays otherwise."""
        if self._pipeline:
            rec = self._pipeline[-1]
            return rec.toks[-1], rec.pos, rec.done, rec.remaining
        return self.tok, self.pos, self.dones, self.remaining

    def _materialize(self, *arrs):
        """np.asarray with the wait accounted to ``host_blocked_s`` — the
        single choke point both loops materialize through, so the bench's
        blocked-time comparison is honest."""
        t0 = time.monotonic()
        with tracing.span("sched.sync"):
            out = [np.asarray(a) for a in arrs]
        now = time.monotonic()
        self.stats["host_blocked_s"] += now - t0
        # liveness: engine outputs just became host-visible — the watchdog
        # signal /health reports (a wedged device stops advancing this)
        self._progress_t = now
        return out[0] if len(out) == 1 else out

    def _run_decode(self, n: int):
        """Engine dispatch for one fused block (overridden by the paged
        backend to thread block tables)."""
        tok, pos, dones, remaining = self._decode_inputs()
        return self.engine.decode_slots(
            self.caches, tok, pos, dones, remaining,
            self.eos, self._next_rng(), n=n)

    def _ensure_capacity(self, n: int) -> None:
        """Pre-decode capacity hook (paged backend: block allocation)."""

    def _decode_block(self, n: int) -> None:
        with tracing.span("sched.capacity"):
            self._ensure_capacity(n)
        toks, self.caches, pos, done, remaining = self._dispatch(
            "decode", lambda: self._run_decode(n), n)
        self._apply_decode(self._materialize(toks), pos, done, remaining, n)

    # -- overlapped loop (dispatch-ahead + one-step-late landing) -----------
    def _dispatch_block(self, n: int) -> None:
        """Dispatch one fused decode block WITHOUT landing it: outputs stay
        device futures in a ``_Pending`` record, and the host state arrays
        advance on a prediction (budget decrements are exact; a landed EOS
        is the only surprise, rolled back at ``_land_next``).  The virtual
        clock advances at dispatch so arrival admissibility matches the
        blocking loop decision-for-decision."""
        with tracing.span("sched.capacity"):
            self._ensure_capacity(n)
        active = (~self.dones) & (self.remaining > 0)
        if not self._pipeline:
            # pipeline was drained: the host arrays ARE the exact frontier
            self._exact_tok = self.tok.copy()
            self._exact_pos = self.pos.copy()
            self._exact_dones = self.dones.copy()
            self._exact_rem = self.remaining.copy()
        toks, self.caches, pos, done, remaining = self._dispatch(
            "decode", lambda: self._run_decode(n), n)
        self._pipeline.append(_Pending(
            toks=toks, pos=pos, done=done, remaining=remaining, n=n,
            base_step=self.step_count,
            slots=list(self.slots), eos=self.eos.copy(), active=active,
            adm_mark=self._admission_mark,
            itl_anchor=(self._last_step_t if self._stamp_itl_at_dispatch
                        else None),
            dispatch_t=time.monotonic()))
        self._admission_mark = False
        # predicted frontier: EOS-blind replay of the device's masking
        steps = np.where(active, np.minimum(n, self.remaining), 0)
        self.pos = (self.pos + steps).astype(np.int32)
        self.remaining = (self.remaining - steps).astype(np.int32)
        self.dones = self.dones | (self.remaining <= 0)
        self.step_count += n
        self.stats["decode_steps"] += n
        self.stats["slot_steps"] += n * self.B
        depth = len(self._pipeline)
        if depth > 1:
            self.stats["dispatch_ahead_steps"] += n
        self.stats["max_dispatch_ahead"] = max(
            self.stats["max_dispatch_ahead"], depth)

    def _land_next(self) -> None:
        """Materialize the OLDEST unlanded block and run its host
        bookkeeping: replay emissions exactly as the blocking loop's
        ``_apply_decode`` (same appends, same on_token order, same stats),
        stamp ITL at host-visibility, then reconcile the predicted state —
        slots the device froze early (EOS) are rolled back in the predicted
        arrays so later admission/capacity decisions see the truth."""
        if not self._pipeline:
            return
        rec = self._pipeline.popleft()
        t0 = time.monotonic()
        self.stats["host_overlap_s"] += t0 - rec.dispatch_t
        toks, pos, done, remaining = self._materialize(
            rec.toks, rec.pos, rec.done, rec.remaining)
        self.stats["landings"] += 1
        if self.faults:
            toks = self.faults.corrupt_tokens(
                toks, rec.base_step,
                active=(np.array([s.req is not None for s in rec.slots])
                        & ~self._exact_dones & (self._exact_rem > 0)))
        with tracing.span("sched.emit"):
            # exact emission replay off the rolling landed pre-state
            cur_done = self._exact_dones.copy()
            cur_rem = self._exact_rem.copy()
            emitted_block = 0
            poisoned: Dict[int, int] = {}
            for s in range(rec.n):
                for i, slot in enumerate(rec.slots):
                    if slot.req is None or cur_done[i] or cur_rem[i] <= 0:
                        continue
                    t = _tok_scalar(toks[s, i])
                    if not 0 <= t < self.vocab:
                        # poisoned step output: freeze the slot NOW so no
                        # later token from this block reaches its stream;
                        # quarantine below, after the exact frontier is
                        # adopted
                        poisoned[i] = t
                        cur_done[i] = True
                        continue
                    slot.toks.append(
                        t if self.ncb == 1
                        else np.asarray(toks[s, i], np.int32).copy())
                    if self.on_token is not None:
                        self.on_token(slot.req.rid, t)
                    cur_rem[i] -= 1
                    if cur_rem[i] == 0 or (rec.eos[i] >= 0
                                           and t == rec.eos[i]):
                        cur_done[i] = True
                    self.stats["emitted"] += 1
                    self.stats["active_slot_steps"] += 1
                    self._tps.append(1)
                    emitted_block += 1
        # the landed arrays are the exact post-block frontier.  The device
        # never learns about host-forced retirements (quarantine/timeout),
        # so its done-mask is OR-ed with the forced set — otherwise a block
        # dispatched before the retirement would resurrect the dead slot.
        self._exact_tok = toks[-1].copy()
        self._exact_pos = np.array(pos)
        self._exact_dones = np.array(done) | self._forced_done
        self._exact_rem = np.where(self._forced_done, 0,
                                   np.array(remaining)).astype(np.int32)
        # one-step rollback: prediction thought these slots were still
        # decoding, but a landed token was EOS — adopt the frozen truth so
        # retire/admission/capacity decisions stop overshooting
        fix = self._exact_dones & ~self.dones
        if fix.any():
            self.stats["eos_rollbacks"] += int(fix.sum())
            self.dones = self.dones | fix
            self.remaining = np.where(fix, self._exact_rem,
                                      self.remaining).astype(np.int32)
            self.pos = np.where(fix, self._exact_pos,
                                self.pos).astype(np.int32)
        if not self._pipeline:
            # fully landed: predicted == exact (incl. the token frontier)
            self.tok = self._exact_tok.copy()
            self.pos = self._exact_pos.copy()
            self.dones = self._exact_dones.copy()
            self.remaining = self._exact_rem.copy()
        # ITL stamps at host-visibility (satellite: never at dispatch);
        # disagg anchors the sample at its own dispatch so the sample stays
        # the decode dispatch's duration (see DisaggScheduler docstring)
        if rec.itl_anchor is not None:
            self._last_step_t = rec.itl_anchor
        self._admission_mark = rec.adm_mark
        self._note_itl(rec.n, emissions=emitted_block)
        for i, t in poisoned.items():
            self._quarantine_slot(
                i, "error", f"poisoned step output (token {t})")
        # retire replays in LANDED-BLOCK order, mirroring the blocking
        # loop's after-every-block retire scan: a request whose final block
        # just landed retires here (its rows are inactive in every still-
        # unlanded record when predictions were exact), so sync and overlap
        # retire requests in the same order, not batched up at round tops
        self._retire()

    def _drain_pipeline(self) -> None:
        """Land every unlanded block (host state becomes exact).  Called
        before any host decision that must merge exact values into the
        engine state: admission, mixed/chunk steps, spec drafting,
        migrations, preemption."""
        while self._pipeline:
            self._land_next()

    def _apply_decode(self, toks, pos, done, remaining, n: int) -> None:
        """Host bookkeeping for ``n`` executed decode steps (toks (n, B)):
        replay the device's masking rule to tell real emissions from
        frozen-slot repeats; final state must agree with the device's."""
        with tracing.span("sched.emit"):
            if self.faults:
                toks = self.faults.corrupt_tokens(
                    toks, self.step_count,
                    active=(np.array([s.req is not None
                                      for s in self.slots])
                            & ~self.dones & (self.remaining > 0)))
            cur_done = self.dones.copy()
            cur_rem = self.remaining.copy()
            emitted_block = 0
            poisoned: Dict[int, int] = {}
            for s in range(n):
                for i, slot in enumerate(self.slots):
                    if slot.req is None or cur_done[i] or cur_rem[i] <= 0:
                        continue
                    t = _tok_scalar(toks[s, i])
                    if not 0 <= t < self.vocab:
                        poisoned[i] = t
                        cur_done[i] = True
                        continue
                    slot.toks.append(
                        t if self.ncb == 1
                        else np.asarray(toks[s, i], np.int32).copy())
                    if self.on_token is not None:
                        self.on_token(slot.req.rid, t)
                    cur_rem[i] -= 1
                    if cur_rem[i] == 0 or (self.eos[i] >= 0
                                           and t == self.eos[i]):
                        cur_done[i] = True
                    self.stats["emitted"] += 1
                    self.stats["active_slot_steps"] += 1
                    self._tps.append(1)
                    emitted_block += 1
            self.tok = toks[-1].copy()
            self.pos = np.array(pos)
            self.dones = np.array(done)
            self.remaining = np.array(remaining)
            self.step_count += n
            self.stats["decode_steps"] += n
            self.stats["slot_steps"] += n * self.B
            self._note_itl(n, emissions=emitted_block)
            for i, t in poisoned.items():
                self._quarantine_slot(
                    i, "error", f"poisoned step output (token {t})")

    def _note_itl(self, n: int, emissions: Optional[int] = None,
                  tokens_per_slot: Optional[List[int]] = None) -> None:
        """Record decode inter-token latency: ONE sample per emitted token,
        not per engine step, so plain and speculative runs weight the
        distribution identically.  Plain masked decode: every token in a
        fused block of ``n`` steps (``emissions`` of them) experienced the
        block's uniform per-step share (host timing cannot see inside the
        block).  A speculative verify step emits a variable run per slot
        (``tokens_per_slot``): a slot that emitted e tokens in a T-second
        step experienced per-token latency T/e, so it contributes e samples
        of T/e — without this, multi-token steps would overstate ITL by the
        acceptance factor.  Samples whose interval spans admission work (a
        whole-prompt prefill call since the previous decode step, or a
        mixed chunk step) are tagged as admission-window samples — the
        population whose p95 chunked prefill exists to flatten."""
        now = time.monotonic()
        if self._last_step_t is not None:
            dt = (now - self._last_step_t) / n
            self._itl_recent.append(dt)
            if tokens_per_slot is None:
                m = n if emissions is None else emissions
                self._itl.extend([(dt, self._admission_mark)] * m)
            else:
                for e in tokens_per_slot:
                    if e > 0:
                        self._itl.extend([(dt / e, self._admission_mark)] * e)
        self._last_step_t = now
        self._admission_mark = False

    # -- failure isolation (quarantine, bounded retry, deadlines) -----------
    def liveness_age(self) -> float:
        """Seconds since engine outputs last became host-visible — the
        scheduler-watchdog signal the frontend's /health surfaces so a load
        balancer can eject a wedged node."""
        return time.monotonic() - self._progress_t

    def _release_slot(self, i: int) -> None:
        """Backend storage release for slot ``i`` (paged: blocks/refcounts;
        disagg: queued copies unpinned, destination blocks returned).  The
        dense engine owns nothing per slot."""

    def _quarantine_slot(self, i: int, finish_reason: str = "error",
                         error: Optional[str] = None) -> None:
        """Retire slot ``i``'s request IMMEDIATELY with a failure
        finish_reason, releasing everything it holds, without touching any
        other slot's stream.  Safe while blocks are still in flight: the
        forced-done mask keeps landed device state from resurrecting the
        slot, and already-dispatched programs reading its freed blocks are
        harmless — the device executes in dispatch order, so those reads
        complete before any later program could overwrite them."""
        s = self.slots[i]
        if s.req is None:
            return
        r = s.req
        self._release_slot(i)
        r.output = np.asarray(s.toks, dtype=np.int32)
        r.finish_reason = finish_reason
        if error is not None:
            r.stats["error"] = error
        r.stats.update({
            "emitted": len(s.toks),
            "finished_at": time.monotonic(),
            "decode_steps_held": self.step_count - s.admitted_step,
        })
        self.slots[i] = _Slot()
        self.tok[i] = 0
        self.dones[i] = True
        self.remaining[i] = 0
        self._forced_done[i] = True
        if self._pipeline and self._exact_dones is not None:
            self._exact_tok[i] = 0
            self._exact_dones[i] = True
            self._exact_rem[i] = 0
        self.stats["timeouts" if finish_reason == "timeout"
                   else "quarantined"] += 1
        if self.faults:
            self.faults.on_quarantine(i)
        self._finish(r)

    def _fault_dispatch(self) -> None:
        """Installed as ``Engine.dispatch_hook`` when a fault plan is
        active: consulted immediately before every retry-safe step dispatch
        (the only boundary where a raise leaves the donated cache chain
        untouched — see ``runtime/faults.py``)."""
        self.faults.on_dispatch(self.step_count)

    def _try_step(self, fn):
        """Run one engine-step thunk under the bounded-retry fault policy.

        A :class:`TransientStepError` at the dispatch boundary consumed no
        state: the step's rng draw is rolled back (``_next_rng`` evaluates
        as a call argument, before the engine method's hook runs), the
        pipeline is drained to the exact landed frontier, and the round
        simply ends — the next round re-issues the identical work, so the
        replay is bit-exact by construction.  When retries exhaust, a
        slot-attributed failure quarantines that request; an unattributed
        one propagates (honestly fatal)."""
        calls0 = self._calls
        try:
            out = fn()
        except TransientStepError as e:
            self._calls = calls0
            self._recover_step_fault(e)
            return None
        self._retry_streak = 0
        return out

    def _recover_step_fault(self, e: TransientStepError) -> None:
        self._drain_pipeline()
        self.stats["step_faults"] += 1
        self._retry_streak += 1
        if self._retry_streak <= self.max_step_retries:
            self.stats["step_retries"] += 1
            backoff = self.retry_backoff_s * (2 ** (self._retry_streak - 1))
            if backoff > 0:
                time.sleep(backoff)
            return
        self._retry_streak = 0
        slot = e.slot
        if slot is not None and self.slots[slot].req is not None:
            self._quarantine_slot(slot, "error",
                                  f"persistent step failure: {e}")
            return
        raise e

    def _expire_deadlines(self) -> None:
        """Retire every request whose wall-clock deadline passed —
        queued (never admitted: empty output) or slot-resident (keeps the
        tokens emitted so far) — with finish_reason "timeout".  No pipeline
        drain needed: the forced-done mask drops the victim's unlanded
        emissions at landing."""
        now = time.monotonic()

        def late(r: Request) -> bool:
            return (r.deadline_s is not None
                    and now - r.submitted_at >= r.deadline_s)

        for r in [r for r in self.queue if late(r)]:
            self.queue.remove(r)
            r.output = np.zeros((0,), np.int32)
            r.finish_reason = "timeout"
            r.stats.update({"emitted": 0, "finished_at": now})
            self.stats["timeouts"] += 1
            self._finish(r)
        for i, s in enumerate(self.slots):
            if s.req is not None and late(s.req):
                self._quarantine_slot(i, "timeout")

    # -- overload resilience (burst injection + degradation ladder) ---------
    def _inject_bursts(self) -> None:
        """Submit the fault plan's due ``burst:`` clauses: deterministic
        synthetic load (prompts seeded by the scheduled fire step) arriving
        NOW on the virtual clock — the reproducible overload wave the
        degradation tests ride."""
        for count, plen, max_new, cls, fire_step in \
                self.faults.burst(self.step_count):
            rng = np.random.default_rng(0xB0057 + fire_step)
            plen = min(plen, self.prompt_limit,
                       max(2, self.engine.max_len - max_new))
            for _ in range(count):
                self.submit(rng.integers(0, self.vocab, plen,
                                         dtype=np.int32),
                            max_new, arrival_step=self.step_count,
                            priority=cls)
                self.stats["burst_injected"] += 1

    def _overload_observe(self) -> None:
        """One controller observation per round: arrived-queue depth (the
        deterministic primary signal) plus the recent landed per-step ITL
        window (advisory, SLO-scaled)."""
        ctl = self.overload_ctl
        depth = sum(1 for r in self.queue
                    if r.arrival_step <= self.step_count)
        recent = (float(np.mean(self._itl_recent))
                  if self._itl_recent else None)
        before = ctl.level
        ctl.observe(depth, recent)
        if ctl.level != before:
            self.stats["overload_transitions"] += 1

    def _round_prologue(self) -> None:
        """Shared head of every serving round (unified and disagg): burst
        injection, deadline expiry, then one degradation-controller
        observation."""
        if self.faults:
            self._inject_bursts()
        if self._has_deadlines:
            self._expire_deadlines()
        if self.overload_ctl is not None:
            self._overload_observe()

    def _spec_suspended(self) -> bool:
        """True while the degradation ladder has turned spec decode off
        (level 2+).  Safe lever: greedy spec decode is token-identical to
        plain decode, so suspension changes speed, never streams."""
        ctl = self.overload_ctl
        if ctl is not None and ctl.spec_off:
            self.stats["spec_off_rounds"] += 1
            return True
        return False

    def overload_level(self) -> int:
        """Current degradation-ladder level (0 = normal / controller off);
        the frontend's ``/health`` surfaces this."""
        return 0 if self.overload_ctl is None else self.overload_ctl.level

    # -- speculative decoding (fused multi-token verify steps) -------------
    def _active_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots)
                if s.req is not None and not self.dones[i]
                and self.remaining[i] > 0]

    def _slot_history(self, i: int) -> np.ndarray:
        """The slot's prompt+generated token history for the drafter,
        maintained incrementally: the prompt copies in once, emitted tokens
        (spec runs AND mixed-step decode emissions) append as they land."""
        s = self.slots[i]
        plen = len(s.req.prompt)
        if s.hist is None:
            s.hist = np.empty(plen + s.req.max_new + 1, np.int32)
            s.hist[:plen] = np.asarray(s.req.prompt, np.int32).ravel()
            s.hist_len = plen
        total = plen + len(s.toks)
        if s.hist_len < total:               # catch up on new emissions
            s.hist[s.hist_len:total] = s.toks[s.hist_len - plen:]
            s.hist_len = total
        return s.hist[:s.hist_len]

    def _ensure_spec_capacity(self) -> None:
        """Pre-verify capacity hook (paged: blocks for spec_k+1 writes)."""

    def _run_verify(self, vtok):
        return self.engine.verify_slots(
            self.caches, vtok, self.pos, self.dones, self.remaining,
            self.eos, self._next_rng())

    def _post_verify(self, active: List[int]) -> None:
        """Post-verify hook (paged: truncate block tables past the rewound
        frontier so resident memory tracks accepted tokens, not drafts)."""

    def _spec_step(self) -> None:
        """One speculative serving step: draft spec_k tokens per active slot
        from its own history (host n-gram lookup), verify all of them plus
        the bonus position in ONE fused forward, emit each slot's accepted
        run.  Every step emits at least one token per active slot (the
        zero-acceptance floor is exactly plain decode), at most spec_k+1.

        Unlike plain decode, verify steps are not fused into multi-step
        blocks: each step's drafts depend on the tokens the previous step
        emitted, so the drafter sits on the host between steps (block_steps
        does not apply while spec decode is on)."""
        K = self.spec_k
        with tracing.span("sched.capacity"):
            self._ensure_spec_capacity()   # may preempt: collect slots AFTER
        active = self._active_slots()
        if not active:
            return
        vtok = np.zeros((self.B, K + 1), np.int32)
        vtok[:, 0] = self.tok
        histories = [self._slot_history(i) for i in active]
        if hasattr(self.drafter, "propose_many"):
            vtok[active, 1:] = self.drafter.propose_many(histories)
        else:                     # per-slot drafters (the pre-batch API)
            vtok[active, 1:] = np.stack(
                [self.drafter.propose(h) for h in histories])
        targets, n_emit, nxt, self.caches, pos, done, remaining = \
            self._dispatch("spec", lambda: self._run_verify(vtok))
        targets, n_emit = self._materialize(targets, n_emit)
        counts = []
        for i in active:
            e = int(n_emit[i])
            slot = self.slots[i]
            for t in targets[i, :e].tolist():
                slot.toks.append(int(t))
                if self.on_token is not None:
                    self.on_token(slot.req.rid, int(t))
            counts.append(e)
            self._tps.append(e)
            # acceptance counts drafts the model VERIFIED correct (leading
            # match run), independent of EOS/budget cuts to the emitted
            # run — otherwise short-budget slots would bias the rate low
            match = vtok[i, 1:] == targets[i, :-1]
            acc = int(np.cumprod(match).sum())
            self.stats["emitted"] += e
            self.stats["active_slot_steps"] += 1
            self.stats["spec_slot_steps"] += 1
            self.stats["spec_emitted"] += e
            self.stats["spec_accepted"] += acc
        self.tok = np.asarray(nxt).copy()
        self.pos = np.array(pos)
        self.dones = np.array(done)
        self.remaining = np.array(remaining)
        self.step_count += 1
        self.stats["decode_steps"] += 1
        self.stats["slot_steps"] += self.B
        self.stats["spec_steps"] += 1
        self.stats["spec_proposed"] += K * len(active)
        self._note_itl(1, tokens_per_slot=counts)
        self._post_verify(active)

    # -- chunked admission (fused mixed prefill/decode steps) --------------
    def _prefilling(self) -> List[int]:
        return [i for i, s in enumerate(self.slots)
                if s.req is not None and s.chunk_next is not None]

    def _pre_mixed(self) -> None:
        """Pre-step capacity hook (paged: decode block coverage)."""

    def _run_mixed(self, tokens, admit, first, clens, starts, totals):
        return self.engine.mixed_step(
            self.caches, tokens, admit, first, clens, starts, totals,
            self.tok, self.pos, self.dones, self.remaining, self.eos,
            self._next_rng())

    def _post_chunks(self, slots_p: List[int]) -> None:
        """Hook after each chunk lands (paged: publish completed prefix
        blocks incrementally)."""

    def _mixed_step(self) -> None:
        """One fused chunked-admission step: every mid-prefill slot advances
        one fixed-width chunk while all decode-active slots decode one token
        — in the same jitted program, so long-prompt admission costs decode
        at most one chunk of extra latency per token."""
        C = self.chunk
        with tracing.span("sched.capacity"):
            self._pre_mixed()              # may preempt: assemble AFTER
        slots_p = self._prefilling()
        if not slots_p:
            # capacity pressure preempted every prefilling slot — nothing to
            # chunk this turn; the main loop falls through to plain decode
            return
        tokens = np.full((self.B, C), self.pad_id, np.int32)
        admit = np.zeros((self.B,), bool)
        first = np.zeros((self.B,), bool)
        clens = np.ones((self.B,), np.int32)
        starts = np.zeros((self.B,), np.int32)
        totals = np.ones((self.B,), np.int32)
        emits = []
        for i in slots_p:
            s = self.slots[i]
            off = s.chunk_next
            plen = len(s.req.prompt)
            nc = min(C, plen - off)
            tokens[i, :nc] = s.req.prompt[off:off + nc]
            admit[i] = True
            first[i] = not s.chunk_started
            clens[i] = nc
            starts[i] = off
            totals[i] = off + nc
            if off + nc == plen:
                emits.append(i)
        ptok, toks, self.caches, pos, done, remaining = self._dispatch(
            "mixed", lambda: self._run_mixed(tokens, admit, first, clens,
                                             starts, totals),
            chunks=[(int(starts[i]), int(clens[i])) for i in slots_p])
        self._admission_mark = True        # this step carried prefill work
        self._apply_decode(self._materialize(toks)[None], pos, done,
                           remaining, 1)
        for i in slots_p:
            s = self.slots[i]
            s.chunk_started = True
            s.chunk_next += int(clens[i])
            self.stats["prefill_tokens"] += int(clens[i])
            self.stats["prefill_chunks"] += 1
        self._post_chunks(slots_p)
        if emits:
            adm = np.zeros((self.B,), bool)
            adm[emits] = True
            self._finish_admission(emits, [self.slots[i].req for i in emits],
                                   adm, np.array(ptok))
            for i in emits:
                self.slots[i].chunk_next = None

    def _block_size(self) -> int:
        """Fused block size in {1,2,4,...,block_steps}.

        A slot that finishes inside a fused block burns masked steps until
        the block ends: nearly free compute (the batch width is fixed), but
        the freed slot cannot be refilled until the next host turn.  Two
        policies, measured head-to-head on the straggler bench:

        * amortizing (default): stretch to the LONGEST active budget —
          fewest host dispatches; admission waits at most block_steps.
          Wins wall-clock when per-step compute is cheap relative to
          dispatch (this CPU container: 1.6x vs 1.4x over the wave
          baseline).
        * responsive (``responsive_blocks=True``): while arrived requests
          wait, bound by the SHORTEST budget (floored at block_steps/4 to
          cap dispatch thrash) so finished slots refill immediately —
          fewer total decode steps and higher slot utilization (84% vs
          77%, 149 vs 163 steps on the bench); wins when a decode step
          dominates dispatch, i.e. real model scale."""
        active = self.remaining[(~self.dones) & (self.remaining > 0)]
        if active.size == 0:
            return 0
        waiting = any(r.arrival_step <= self.step_count for r in self.queue)
        if self.responsive_blocks and waiting:
            need = max(int(active.min()), max(1, self.block_steps // 4))
        else:
            need = int(active.max())
        n = 1
        while n * 2 <= min(self.block_steps, need):
            n *= 2
        return n

    def request_summary(self) -> Dict:
        """Aggregate per-request latency stats (TTFT + queue wait) over the
        completed set, plus the decode inter-token latency distribution —
        overall and restricted to admission windows (steps whose interval
        absorbed prefill work).  Per-request numbers live in
        ``Request.stats``; under chunked admission ``ttft_s`` is stamped at
        the chunk that completed the prompt (first *emitted* token).

        **``_last_step_t`` semantics.**  ITL samples are intervals between
        successive ``_note_itl`` stamps, and a stamp is ALWAYS taken when
        tokens become host-visible — after ``np.asarray`` returns, i.e. at
        ``_apply_decode`` in the blocking loop and at ``_land_next`` in the
        overlapped loop — never at dispatch, which under overlap would
        report the near-zero time to *queue* a block rather than the time
        its tokens took to exist.  ``DisaggScheduler`` additionally anchors
        the interval's start at its own decode dispatch (``itl_anchor``)
        so the sample stays the decode dispatch's duration, excluding
        same-round prefill-pool host time (see its class docstring); the
        end of the interval is still the landing.

        The ``overlap`` section reports the host/device timing split for
        either loop: ``host_blocked_s`` (total np.asarray wait),
        ``host_overlap_s`` (host work done between a dispatch and its
        landing), the derived overlap fraction and per-step blocked time,
        dispatch-ahead depth, EOS rollbacks, and frontend shed count."""
        out: Dict = {"requests": len(self.done)}
        for key in ("ttft_s", "queue_s"):
            s = percentile_summary(r.stats[key] for r in self.done
                                   if key in r.stats)
            if s is not None:
                out[key] = s
        if self._itl:
            out["decode_itl_s"] = percentile_summary(d for d, _ in self._itl)
            adm = percentile_summary(d for d, a in self._itl if a)
            if adm is not None:
                out["decode_itl_admission_s"] = adm
        if self._tps:
            out["tokens_per_step"] = percentile_summary(self._tps)
        hb = self.stats["host_blocked_s"]
        ho = self.stats["host_overlap_s"]
        out["overlap"] = {
            "enabled": self.overlap,
            "host_blocked_s": hb,
            "host_overlap_s": ho,
            "host_overlap_fraction": (ho / (ho + hb) if ho + hb > 0 else 0.0),
            "host_blocked_per_step_s": (
                hb / max(1, self.stats["decode_steps"])),
            "landings": self.stats["landings"],
            "dispatch_ahead_steps": self.stats["dispatch_ahead_steps"],
            "max_dispatch_ahead": self.stats["max_dispatch_ahead"],
            "eos_rollbacks": self.stats["eos_rollbacks"],
            "shed_requests": self.stats["shed_requests"],
        }
        if self.stats.get("spec_steps"):
            prop = self.stats["spec_proposed"]
            slot_steps = max(1, self.stats["spec_slot_steps"])
            out["spec"] = {
                "steps": self.stats["spec_steps"],
                # fraction of proposed drafts the model verified correct
                # (leading match run, independent of EOS/budget cuts)
                "acceptance_rate": (self.stats["spec_accepted"] / prop
                                    if prop else 0.0),
                # tokens emitted per (verify step, active slot): the
                # speedup factor over plain one-token decode (floor 1.0)
                "mean_tokens_per_step": (self.stats["spec_emitted"]
                                         / slot_steps),
                # drafts verified correct per (verify step, active slot)
                "mean_accepted_per_step": (self.stats["spec_accepted"]
                                           / slot_steps),
            }
        fr: Dict[str, int] = {}
        for r in self.done:
            key = r.finish_reason or "length"
            fr[key] = fr.get(key, 0) + 1
        out["finish_reasons"] = fr
        fkeys = ("step_faults", "step_retries", "quarantined", "timeouts",
                 "aborts_exhaustion", "livelock_aborts", "migration_faults")
        if any(self.stats.get(k) for k in fkeys):
            out["faults"] = {k: self.stats.get(k, 0) for k in fkeys}
        classes = self._class_summary()
        if classes:
            out["classes"] = classes
        if self.overload_ctl is not None:
            out["overload"] = self.overload_ctl.summary()
        return out

    def _class_summary(self) -> Dict:
        """Per-priority-class latency breakdown over the completed set:
        outcome counters, TTFT percentiles, per-request decode ITL
        percentiles (token cadence between first emission and completion),
        and — when the class carries an SLO target — the attainment
        fraction: completed requests (finish_reason stop/length) whose
        per-token latency ``(finished_at - submitted_at) / emitted`` met
        the target, over ALL retired requests of the class, so shed and
        timed-out requests count against attainment."""
        counters = self.stats.get("classes", {})
        classes: Dict = {}
        for cls in PRIORITY_CLASSES:
            recs = [r for r in self.done if r.priority == cls]
            counts = counters.get(cls, {})
            if not recs and not any(counts.values()):
                continue
            entry: Dict = {"requests": len(recs)}
            entry.update(counts)
            s = percentile_summary(r.stats["ttft_s"] for r in recs
                                   if "ttft_s" in r.stats)
            if s is not None:
                entry["ttft_s"] = s
            s = percentile_summary(
                (r.stats["finished_at"] - r.submitted_at - r.stats["ttft_s"])
                / (r.stats["emitted"] - 1)
                for r in recs
                if r.stats.get("emitted", 0) >= 2 and "ttft_s" in r.stats
                and "finished_at" in r.stats)
            if s is not None:
                entry["itl_s"] = s
            target = float(self.slo_targets.get(cls) or 0.0)
            if target > 0 and recs:
                ok = sum(1 for r in recs
                         if r.finish_reason in ("stop", "length")
                         and r.stats.get("emitted", 0) > 0
                         and "finished_at" in r.stats
                         and (r.stats["finished_at"] - r.submitted_at)
                         / r.stats["emitted"] <= target)
                entry["slo_target_s"] = target
                entry["slo_attainment"] = ok / len(recs)
            classes[cls] = entry
        return classes

    def _init_caches(self) -> None:
        # ring caches get spec_k slack entries so a verify chunk of K
        # drafts never wraps onto live window history
        self.caches = self.engine.init_slot_caches(
            self.B, ring_slack=self.spec_k)

    # -- main loop --------------------------------------------------------
    def _serve_round(self) -> bool:
        """One scheduler round (retire → admit → step); returns False when
        fully idle — no unlanded block, no busy slot, no queued request.

        The overlapped loop's shape: drain the pipeline only when this
        round must merge exact host values into the engine state (an
        admission could fill a slot, a chunk/spec step reads the token
        frontier); otherwise dispatch the next block on the previous
        block's device futures, THEN land the older block — np.asarray
        waits only for a block whose successor is already queued on the
        device."""
        self._round_prologue()
        if self._pipeline and any(r.arrival_step <= self.step_count
                                  for r in self.queue):
            # an arrival could admit once done slots retire: land first so
            # admission sees the same frontier the blocking loop would
            if any(s.req is None or (self.dones[i] and s.chunk_next is None)
                   for i, s in enumerate(self.slots)):
                self._drain_pipeline()
        self._retire_and_admit()
        if self._prefilling():
            # chunked admission in flight: fused mixed steps advance one
            # chunk per slot AND one decode token per active slot (reads
            # the host token frontier — exact state required)
            self._drain_pipeline()
            self._try_step(self._mixed_step)
            return True
        n = self._block_size()
        if n == 0:
            if self._pipeline:
                self._land_next()     # tail blocks land before going idle
                return True
            pending = [r.arrival_step for r in self.queue]
            if not pending:
                return False
            # idle: jump the virtual clock to the next arrival
            self.step_count = max(self.step_count, min(pending))
            return True
        if self.spec_k and not self._spec_suspended():
            # the drafter consumes the previous step's landed tokens, so
            # spec verify steps cannot dispatch ahead — they run blocking
            self._drain_pipeline()
            self._try_step(self._spec_step)
        elif self.overlap:
            self._try_step(lambda: self._overlap_turn(n))
        else:
            self._try_step(lambda: self._decode_block(n))
        return True

    def _retire_and_admit(self) -> None:
        with tracing.span("sched.retire"):
            self._retire()
        with tracing.span("sched.admit") as sp:
            sp.set_metadata(admitted=self._admit())

    def _round(self) -> bool:
        """``_serve_round`` inside its ``repro.sched.round`` span, for
        ``serve_step`` and ``run`` alike."""
        with tracing.span("sched.round", step=self.step_count,
                          t_mono_ns=time.monotonic_ns):
            return self._serve_round()

    def _overlap_turn(self, n: int) -> None:
        self._dispatch_block(n)
        while len(self._pipeline) > 1:
            self._land_next()

    def serve_step(self) -> bool:
        """One scheduler round for external drivers (the asyncio frontend):
        admits anything queued, advances the engine one round, retires, and
        returns False when there is nothing left to do.  Safe to call again
        after new ``submit``s."""
        if self.caches is None:
            self._init_caches()
        return self._round()

    def run(self) -> List[Request]:
        """Serve until queue and slots drain; returns requests in completion
        order."""
        if self.caches is None:
            self._init_caches()
        while self._round():
            pass
        self._drain_pipeline()
        self._retire()
        return self.done


# ---------------------------------------------------------------------------
# Paged continuous batching (block-table backend)
# ---------------------------------------------------------------------------


class PagedContinuousScheduler(ContinuousScheduler):
    """Continuous batching over the paged KV backend.

    Same admit -> step -> retire loop as the dense slot engine, plus
    host-side block management (``kvcache.BlockAllocator``):

    * **block-aware admission** — a request is admitted only when enough
      free blocks exist for its full prompt (+ matched shared-prefix blocks
      are referenced instead of re-prefilled: the suffix alone is computed,
      which is where the prefill-token saving comes from);
    * **incremental allocation** — decode claims the next block only when a
      slot's position crosses a block boundary, so resident memory tracks
      ACTUAL occupancy, not ``n_slots x max_seq``;
    * **preempt-to-requeue** — if the pool is exhausted mid-decode, the
      youngest running request is evicted (blocks freed, request requeued
      for recompute-on-readmission) instead of corrupting the pool;
    * **prefix reuse** — full prompt blocks are published to the allocator's
      hash-chained prefix cache after prefill and dropped when their last
      reference dies.  Only for attention-pure models: recurrent state is
      position-integrated and cannot be grafted from another slot's history.

    ``n_blocks`` defaults to the dense-equivalent footprint
    (n_slots x blocks/slot + nulls); size it SMALLER to overcommit capacity
    against short-request traffic (that is the point of paging).
    """

    def __init__(self, engine: Engine, n_slots: int, pad_id: int = 0,
                 block_steps: int = 8, min_bucket: int = 8,
                 responsive_blocks: bool = False,
                 on_token: Optional[Callable[[int, int], None]] = None,
                 prefill_chunk: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 spec_ngram: Optional[int] = None,
                 overlap: Optional[bool] = None,
                 fault_plan: Optional[str] = None,
                 max_step_retries: Optional[int] = None,
                 retry_backoff_s: Optional[float] = None,
                 slo_targets: Optional[Dict[str, float]] = None,
                 reserve_slots: Optional[int] = None,
                 reserve_blocks: Optional[int] = None,
                 overload_opts: Optional[Dict] = None,
                 *, block_size: Optional[int] = None,
                 n_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 on_preempt: Optional[Callable[[int], None]] = None):
        # paged is a hard backend choice — no silent fallback: the registry
        # raises its uniform error for ring/frontend/multi-codebook archs
        engine.caps.require("paged")
        super().__init__(engine, n_slots, pad_id, block_steps, min_bucket,
                         responsive_blocks, on_token, prefill_chunk,
                         spec_k, spec_ngram, overlap, fault_plan,
                         max_step_retries, retry_backoff_s,
                         slo_targets, reserve_slots, reserve_blocks,
                         overload_opts)
        cfg = engine.cfg
        self.has_attn = any(k in ("attn", "local_attn")
                            for k in cfg.layer_pattern)
        block_size = block_size or engine.parallel.kv_block_size
        self.bs = block_size
        self.view_blocks = -(-engine.max_len // block_size)
        self.n_shards = engine.ctx.dist.dp * engine.ctx.dist.pods
        if n_slots % self.n_shards:
            raise ValueError(f"n_slots {n_slots} must divide data shards "
                             f"{self.n_shards}")
        self.on_preempt = on_preempt
        if n_blocks is None:
            n_blocks = engine.parallel.kv_pool_blocks or None
        if n_blocks is None:
            n_blocks = n_slots * self.view_blocks + self.n_shards
        self.alloc = kvcache.BlockAllocator(n_blocks, block_size,
                                            n_shards=self.n_shards)
        self.n_blocks = n_blocks
        self.prefix_cache = (prefix_cache and self.has_attn
                             and all(k in ("attn", "local_attn")
                                     for k in cfg.layer_pattern))
        # per-slot block table (LOCAL ids; shard_map splits rows by shard);
        # unallocated entries point at the null block 0
        self.bt = np.zeros((n_slots, self.view_blocks), np.int32)
        self.slot_blocks: List[List[int]] = [[] for _ in range(n_slots)]
        self.stats.update({
            "prefill_tokens_saved": 0, "preemptions": 0,
            "shared_block_hits": 0, "blocks_hwm": 0,
        })

    # -- geometry ---------------------------------------------------------
    def _kv_walk(self) -> Optional[Tuple[int, int]]:
        """The paged decode kernel's walk: P pages of ``bs`` tokens a step
        and ceil(view_blocks / P) steps to a slot's whole view, with P from
        the kernel's own helper; None where decode attention does not run
        the kernel (no Pallas, int8 KV, latent or no K/V pools)."""
        par = self.engine.ctx.parallel
        pools = [leaf for path, leaf
                 in jax.tree_util.tree_leaves_with_path(self.caches)
                 if getattr(path[-1], "key", None) == "k"]
        if not (par.use_pallas and pools) or par.kv_quant:
            return None
        P = decode_attention.pages_per_step(
            self.view_blocks, self.engine.ctx.plan.local_kv, self.bs,
            pools[0].shape[-1], pools[0].dtype.itemsize)
        return P * self.bs, -(-self.view_blocks // P)

    def _shard_of(self, slot: int) -> int:
        return slot // (self.B // self.n_shards)

    def _note_usage(self) -> None:
        self.stats["blocks_hwm"] = max(self.stats["blocks_hwm"],
                                       self.alloc.total_used())

    def submit(self, prompt: np.ndarray, max_new: int,
               eos_id: Optional[int] = None, arrival_step: int = 0,
               deadline_s: Optional[float] = None,
               priority: str = "standard") -> int:
        prompt = np.asarray(prompt)
        need = -(-(len(prompt) + max_new) // self.bs)
        usable = self.alloc.blocks_per_shard - 1
        if self.has_attn and need > usable:
            raise ValueError(
                f"request needs {need} blocks > per-shard pool {usable}")
        return super().submit(prompt, max_new, eos_id, arrival_step,
                              deadline_s, priority)

    def _init_caches(self) -> None:
        self.caches = self.engine.init_paged_caches(
            self.B, self.n_blocks, self.bs)

    # -- block management -------------------------------------------------
    def _release_slot(self, i: int) -> None:
        if self.slot_blocks[i]:
            self.alloc.free(self._shard_of(i), self.slot_blocks[i])
            self.slot_blocks[i] = []
        self.bt[i, :] = kvcache.NULL_BLOCK
        self._note_usage()

    def _retire(self) -> None:
        infl = self._inflight_mask()
        for i, s in enumerate(self.slots):
            if (s.req is not None and self.dones[i] and s.chunk_next is None
                    and (infl is None or not infl[i])):
                self._release_slot(i)
        super()._retire()

    def _preempt_youngest(self, shard: int) -> bool:
        """Evict the LOWEST-PRIORITY, most recently admitted running
        request on ``shard`` (victim key: worst class rank, then youngest
        admission, then highest rid — the method keeps its historical name;
        with a single class it degenerates to exactly the old
        youngest-first rule): free its blocks, requeue it (recompute on
        readmission) at the queue head.  Its generated-so-far tokens are
        DISCARDED (recompute restarts from the prompt): the emitted counter
        rolls back, and streaming clients are told via ``on_preempt(rid)``
        to drop what they buffered for that request — under stochastic
        sampling the regenerated stream need not match the discarded one.
        Mid-chunk-prefill slots are also candidates (they hold blocks but
        have emitted nothing); their chunk progress is simply dropped with
        the slot."""
        if self._pipeline:
            # never pick a victim under an unlanded block: its in-flight
            # emissions would replay into a cleared slot, and the evicted
            # state must merge exactly into the engine inputs
            self._drain_pipeline()
        cand = [i for i, s in enumerate(self.slots)
                if s.req is not None and self._shard_of(i) == shard
                and ((not self.dones[i] and self.remaining[i] > 0)
                     or s.chunk_next is not None)]
        if not cand:
            return False
        i = max(cand,
                key=lambda j: (PRIORITY_RANK[self.slots[j].req.priority],
                               self.slots[j].admitted_step,
                               self.slots[j].req.rid))
        req = self.slots[i].req
        self.stats["emitted"] -= len(self.slots[i].toks)
        self._release_slot(i)
        self.slots[i] = _Slot()
        self.dones[i] = True
        self.remaining[i] = 0
        req.stats["preempted"] = req.stats.get("preempted", 0) + 1
        self.queue.insert(0, req)
        self.stats["preemptions"] += 1
        if self.on_preempt is not None:
            self.on_preempt(req.rid)
        return True

    def _grow_slot(self, i: int, n_needed: int) -> bool:
        """Extend slot i's table to cover ``n_needed`` blocks; False if the
        pool cannot supply them."""
        have = len(self.slot_blocks[i])
        if n_needed <= have:
            return True
        if self.faults and self.faults.deny_alloc(self.step_count):
            return False                  # injected pool exhaustion
        fresh = self.alloc.alloc(self._shard_of(i), n_needed - have)
        if fresh is None:
            return False
        for j, b in enumerate(fresh, start=have):
            self.bt[i, j] = b
        self.slot_blocks[i].extend(fresh)
        self._note_usage()
        return True

    def _ensure_capacity(self, n: int) -> None:
        """Before a fused block of ``n`` decode steps, every active slot
        must own blocks covering its writes at pos..pos+min(n, remaining)-1
        (a slot that finishes mid-block freezes; its frozen rewrites are
        covered or harmlessly redirected to the null block).  Allocation
        failure preempts the youngest request on the starved shard and
        retries — the pool is never over-referenced."""
        if not self.has_attn:       # recurrent-only: no pools, nothing to own
            return
        i = 0
        while i < len(self.slots):
            s = self.slots[i]
            if s.req is None or self.dones[i] or self.remaining[i] <= 0:
                i += 1
                continue
            steps = min(n, int(self.remaining[i]))
            need = -(-(int(self.pos[i]) + steps) // self.bs)
            if self._grow_slot(i, need):
                i += 1
                continue
            if self._pipeline:
                # starved while blocks are tied up in unlanded requests:
                # land first (an EOS surprise may free them via retire)
                # before resorting to preemption — and never preempt a slot
                # whose emissions are still in flight
                self._drain_pipeline()
                self._retire()
                continue                   # re-check slot i after landing
            if not self._preempt_youngest(self._shard_of(i)):
                # terminal starvation: no block, nothing evictable.  Abort
                # THIS request (loud counter) instead of killing the serve
                # loop — every other stream keeps decoding
                self.stats["aborts_exhaustion"] += 1
                self._quarantine_slot(
                    i, "error", "paged pool exhausted with nothing to preempt")
                i += 1
                continue
            # re-check slot i (it may itself have been the one evicted)

    def _run_decode(self, n: int):
        tok, pos, dones, remaining = self._decode_inputs()
        return self.engine.decode_slots_paged(
            self.caches, tok, pos, dones, remaining,
            self.eos, self.bt, self._next_rng(), n=n)

    # -- admission --------------------------------------------------------
    def _admit(self) -> int:
        free = self._free_slots()
        arrived = self._admissible()
        if not free or not arrived:
            return 0
        in_flight = any(s.req is not None
                        and (not self.dones[i] or s.chunk_next is not None)
                        for i, s in enumerate(self.slots))
        # block-aware selection: class-ordered arrivals (interactive first,
        # FIFO within a class), stop at the first request whose blocks —
        # or whose claim on the interactive slot/block reserves — don't
        # fit.  Arrivals are class-sorted, so stopping never starves a
        # higher class behind a refused lower one, and the (request, slot)
        # zip pairing stays aligned for the assignment below.
        quota = self._admission_quota(len(free))
        chosen, starts_of = [], {}
        left = len(free)
        for r, slot in zip(arrived, free):
            if len(chosen) >= quota:
                break
            if r.priority != "interactive" and left <= self.reserve_slots:
                break
            if not self.has_attn:   # recurrent-only: no pools to reserve
                starts_of[r.rid] = 0
                chosen.append(r)
                left -= 1
                continue
            shard = self._shard_of(slot)
            plen = len(r.prompt)
            shared, n_cached = [], 0
            if self.prefix_cache:
                shared, n_cached = self.alloc.match_prefix(shard, r.prompt)
                while n_cached > plen - 1:   # keep >=1 suffix token to run
                    shared = shared[:-1]
                    n_cached -= self.bs
            need = -(-plen // self.bs) - len(shared)
            if (r.priority != "interactive"
                    and self.alloc.free_count(shard) - need
                    < self.reserve_blocks):
                # the blocks exist but are held for interactive admissions
                break
            fresh = self.alloc.alloc(shard, need)
            if fresh is None:
                break
            if shared:
                self.alloc.incref(shard, shared)
                self.stats["shared_block_hits"] += len(shared)
            blocks = shared + fresh
            self.slot_blocks[slot] = blocks
            self.bt[slot, :] = kvcache.NULL_BLOCK
            self.bt[slot, :len(blocks)] = blocks
            starts_of[r.rid] = n_cached
            chosen.append(r)
            left -= 1
        if not chosen:
            return 0
        self._note_usage()
        for r in chosen:
            self.queue.remove(r)
        now = time.monotonic()
        short = []
        for slot, r in zip(free, chosen):
            self.slots[slot] = _Slot(req=r, admitted_step=self.step_count)
            self._forced_done[slot] = False
            r.stats["queue_s"] = now - r.submitted_at
            r.stats["admitted_step"] = self.step_count
            r.stats["prefill_tokens_saved"] = starts_of[r.rid]
            self.stats["prefill_tokens_saved"] += starts_of[r.rid]
            if self.chunk:
                # every uncached suffix streams through the mixed step (the
                # first chunk resumes right after the shared prefix); short
                # suffixes complete in one chunk — one compiled admission
                # program, no pow-2 buckets
                self.slots[slot].chunk_next = starts_of[r.rid]
                self.dones[slot] = True
                self.remaining[slot] = 0
                if len(r.prompt) - starts_of[r.rid] > self.chunk:
                    self.stats["chunked_admissions"] += 1
            else:
                short.append((slot, r))
        self.stats["admission_rounds"] += 1
        if in_flight:
            self.stats["in_flight_admissions"] += len(chosen)
        if short:
            self._prefill_suffix(short, starts_of)
        return len(chosen)

    def _prefill_suffix(self, pairs, starts_of) -> None:
        """Legacy single-shot paged admission (suffix within the chunk
        budget): one bucketed full-width prefill through the write table."""
        Lp = self._bucket(max(len(r.prompt) - starts_of[r.rid]
                              for _, r in pairs))
        tokens = np.full((self.B, Lp), self.pad_id, np.int32)
        admit = np.zeros((self.B,), bool)
        plens = np.ones((self.B,), np.int32)
        starts = np.zeros((self.B,), np.int32)
        totals = np.ones((self.B,), np.int32)
        for slot, r in pairs:
            suffix = r.prompt[starts_of[r.rid]:]
            tokens[slot, : len(suffix)] = suffix
            admit[slot] = True
            plens[slot] = len(suffix)
            starts[slot] = starts_of[r.rid]
            totals[slot] = len(r.prompt)
            self.stats["prefill_tokens"] += len(suffix)
        # write table: un-admitted rows are nulled so the full-width prefill
        # scatter cannot touch a live slot's blocks (their pad-token K/V
        # sinks into the null block; their forward output is discarded)
        bt_w = np.where(admit[:, None], self.bt, kvcache.NULL_BLOCK).astype(np.int32)
        new_tok, self.caches = self._dispatch(
            "prefill", lambda: self.engine.prefill_into_slots_paged(
                self.caches, tokens, admit, plens, starts, totals, bt_w,
                self._next_rng()),
            chunks=[(starts_of[r.rid], len(r.prompt) - starts_of[r.rid])
                    for _, r in pairs])
        self._admission_mark = True
        # publish the freshly-prefilled full prompt blocks for reuse
        if self.prefix_cache:
            for slot, r in pairs:
                n_full = len(r.prompt) // self.bs
                self.alloc.register_prefix(self._shard_of(slot), r.prompt,
                                           self.slot_blocks[slot][:n_full])
        self._finish_admission([s for s, _ in pairs], [r for _, r in pairs],
                               admit, np.array(new_tok))

    # -- speculative decoding hooks ---------------------------------------
    def _ensure_spec_capacity(self) -> None:
        # a verify step writes up to spec_k+1 tokens per active slot
        # (accepted or not — rejected writes are rewound afterwards); every
        # slot needs block coverage for the worst case before the step
        self._ensure_capacity(self.spec_k + 1)

    def _run_verify(self, vtok):
        # one table serves both halves of verify: active rows carry their
        # real tables (the chunk scatter AND the stripe gather route through
        # it), frozen rows are nulled so their writes sink into the dead
        # block instead of touching live (possibly mid-admission) blocks
        active = (~self.dones) & (self.remaining > 0)
        bt_w = np.where(active[:, None], self.bt,
                        kvcache.NULL_BLOCK).astype(np.int32)
        return self.engine.verify_slots_paged(
            self.caches, vtok, self.pos, self.dones, self.remaining,
            self.eos, bt_w, self._next_rng())

    def _post_verify(self, active: List[int]) -> None:
        # block-table truncation = the paged half of KV rewind: blocks that
        # _ensure_spec_capacity grabbed for draft positions past the
        # accepted frontier hold only rejected-draft K/V (dead by the
        # position rewind) — return them so resident memory tracks tokens
        # actually accepted, and the freed blocks can serve other slots'
        # admissions immediately.  self.pos is already the rewound
        # frontier: entries [0, pos) are valid, the entry AT pos is written
        # by the next step (whose capacity hook re-grows the table).
        for i in active:
            keep = -(-int(self.pos[i]) // self.bs)
            blocks = self.slot_blocks[i]
            if len(blocks) > keep:
                self.alloc.free(self._shard_of(i), blocks[keep:])
                self.bt[i, keep:len(blocks)] = kvcache.NULL_BLOCK
                self.slot_blocks[i] = blocks[:keep]
        self._note_usage()

    # -- chunked admission hooks ------------------------------------------
    def _pre_mixed(self) -> None:
        # the decode half writes one token per active slot: ensure block
        # coverage first (may preempt — mixed assembly happens after, so an
        # evicted slot simply drops out of this step)
        self._ensure_capacity(1)

    def _run_mixed(self, tokens, admit, first, clens, starts, totals):
        # two tables: the chunk scatter goes through null rows for every
        # non-admitting slot (protecting live blocks), the decode half
        # through the real per-slot tables
        bt_w = np.where(admit[:, None], self.bt,
                        kvcache.NULL_BLOCK).astype(np.int32)
        return self.engine.mixed_step_paged(
            self.caches, tokens, admit, first, clens, starts, totals,
            self.tok, self.pos, self.dones, self.remaining, self.eos,
            bt_w, self.bt, self._next_rng())

    def _post_chunks(self, slots_p: List[int]) -> None:
        # publish prefix blocks INCREMENTALLY: each chunk boundary completes
        # chunk_next // block_size full blocks, reusable immediately by
        # admissions that arrive while the rest of the prompt still streams
        # (register_prefix zips the hash chain against the blocks given, so
        # a partial prefix registers exactly its completed blocks)
        if not self.prefix_cache:
            return
        for i in slots_p:
            s = self.slots[i]
            n_full = s.chunk_next // self.bs
            if n_full:
                self.alloc.register_prefix(self._shard_of(i), s.req.prompt,
                                           self.slot_blocks[i][:n_full])


# ---------------------------------------------------------------------------
# Disaggregated prefill/decode serving
# ---------------------------------------------------------------------------


class DisaggScheduler(PagedContinuousScheduler):
    """Disaggregated serving: the data axis splits into a PREFILL POOL (the
    first ``prefill_shards`` shards) and a DECODE POOL (the rest), each with
    its own per-shard block namespace from the allocator.

    Prompts admit only to prefill-pool slots and stream through the
    chunk-prefill-ONLY program (no decode ride-along — the chunked engine's
    mixed step exists precisely because admission steals decode steps there;
    here decode-active slots live on other shards and step separately).  At
    each published chunk boundary the completed full blocks are EAGERLY
    enqueued for migration; when the prompt completes, the tail block
    follows, the prefill slot is released, and the request lands in a free
    decode-pool slot with its position row rewritten — the same batched
    jitted step that executes the queued device-to-device block copies.
    Refcounts hand off through the allocator: sources are pinned by
    ``begin_migration`` until the copy lands, destinations are owned by the
    landing slot, and a decode-side prefix hit on an already-migrated block
    is referenced instead of copied (``migration_skipped_blocks``).

    Because decode reads K/V only through block-table indirection, the
    decode program never learns where a block was filled: greedy streams
    are token-identical to the unified paged engine (same chunk width, same
    per-row math — batch-row placement is invisible to row-local attention).

    **ITL accounting.**  This single-process container necessarily
    serializes the two pools' dispatches; on the disaggregated deployment
    this models, they run on disjoint shard groups concurrently.  The
    decode-pool ITL therefore measures each decode DISPATCH's own duration
    (``_last_step_t`` is stamped immediately before the decode program, so
    the sample excludes same-round chunk/migration host time) — exactly the
    quantity that stays flat under concurrent prefill load, where the
    unified chunked engine's admission-window ITL absorbs one chunk of
    prefill compute per token.  Rounds that carried prefill work still tag
    their decode samples (``decode_itl_admission_s``), so flatness is
    visible as admission-window p95 ≈ overall p95.
    """

    def __init__(self, engine: Engine, n_slots: int, pad_id: int = 0,
                 block_steps: int = 8, min_bucket: int = 8,
                 responsive_blocks: bool = False,
                 on_token: Optional[Callable[[int, int], None]] = None,
                 prefill_chunk: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 spec_ngram: Optional[int] = None,
                 overlap: Optional[bool] = None,
                 fault_plan: Optional[str] = None,
                 max_step_retries: Optional[int] = None,
                 retry_backoff_s: Optional[float] = None,
                 slo_targets: Optional[Dict[str, float]] = None,
                 reserve_slots: Optional[int] = None,
                 reserve_blocks: Optional[int] = None,
                 overload_opts: Optional[Dict] = None,
                 *, block_size: Optional[int] = None,
                 n_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 on_preempt: Optional[Callable[[int], None]] = None,
                 prefill_shards: Optional[int] = None):
        # the pool split rides on chunked prefill over the paged backend (a
        # prompt must be resumable mid-cache on the prefill shards);
        # ineligible archs would silently serve unified, so the registry
        # rejects them loudly with its uniform error
        engine.caps.require("disagg")
        super().__init__(engine, n_slots, pad_id, block_steps, min_bucket,
                         responsive_blocks, on_token, prefill_chunk,
                         spec_k, spec_ngram, overlap, fault_plan,
                         max_step_retries, retry_backoff_s,
                         slo_targets, reserve_slots, reserve_blocks,
                         overload_opts,
                         block_size=block_size,
                         n_blocks=n_blocks, prefix_cache=prefix_cache,
                         on_preempt=on_preempt)
        # ITL samples anchor at the decode DISPATCH (class docstring); the
        # overlapped landing restores this anchor per record (itl_anchor)
        self._stamp_itl_at_dispatch = True
        # livelock-breaker state (was loop-local before _serve_round)
        self._stall = 0
        self._stall_sig = None
        if not self.chunk:
            raise ValueError("disaggregated serving needs prefill_chunk > 0")
        from repro.launch.mesh import split_data_shards
        pf = (prefill_shards if prefill_shards is not None
              else engine.parallel.disagg_prefill_shards)
        try:
            self._pf_shards, self._dec_shards = split_data_shards(
                self.n_shards, pf)
        except ValueError as e:
            raise ValueError(
                "disaggregated serving splits the data axis into two pools "
                f"(got dp*pods={self.n_shards}, prefill_shards={pf}) — run "
                "with dp >= 2 and 1 <= prefill_shards < dp*pods") from e
        self._spss = self.B // self.n_shards
        self._pf_slots = tuple(range(len(self._pf_shards) * self._spss))
        # migration pipeline state:
        #   queue   (slot, src_shard, src_local, dst_shard, dst_local)
        #           copies awaiting the next batched migrate step
        #   _mig    per-slot handoff state {dst, dst_blocks, sent, ready_t}
        #   _handoff_ready  prefill-complete slots still enqueuing blocks
        #   _landing        fully-enqueued requests awaiting a decode slot
        self._mig_queue: List[Tuple[int, int, int, int, int]] = []
        self._mig: Dict[int, Dict] = {}
        self._handoff_ready: List[int] = []
        self._landing: List[Dict] = []
        from collections import deque
        self._mig_wait: "deque[float]" = deque(maxlen=65536)
        self._block_bytes: Optional[int] = None
        self.stats.update({
            "migrated_blocks": 0, "migration_bytes": 0,
            "migration_skipped_blocks": 0, "migration_deferrals": 0,
            "migration_steps": 0, "handoffs": 0,
            "prefill_steps": 0, "prefill_slot_busy": 0,
            "prefill_slot_total": 0,
        })

    # -- pool geometry ----------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [i for i in self._pf_slots if self.slots[i].req is None]

    def _prefilling(self) -> List[int]:
        # chunk_next == plen is the awaiting-handoff sentinel: the slot is
        # no longer chunking but must not retire until its blocks migrate
        return [i for i in super()._prefilling()
                if self.slots[i].chunk_next < len(self.slots[i].req.prompt)]

    def _pick_decode_shard(self) -> int:
        """Least-loaded decode shard: most free blocks, then most free
        slots, then lowest id (deterministic)."""
        def free_slots(sh: int) -> int:
            lo = sh * self._spss
            return sum(1 for j in range(lo, lo + self._spss)
                       if self.slots[j].req is None)

        return max(self._dec_shards,
                   key=lambda sh: (self.alloc.free_count(sh),
                                   free_slots(sh), -sh))

    # -- slot release / preemption (migration-state cleanup) ---------------
    def _release_slot(self, i: int) -> None:
        m = self._mig.pop(i, None)
        if m is not None:
            # drop this slot's queued copies (unpinning their sources) and
            # return its destination-side blocks — a preempted request
            # recomputes from the prompt on readmission, so any half-done
            # handoff is rolled back whole
            keep = []
            for e in self._mig_queue:
                if e[0] == i:
                    self.alloc.end_migration(e[1], [e[2]])
                else:
                    keep.append(e)
            self._mig_queue[:] = keep
            if m["dst_blocks"]:
                self.alloc.free(m["dst"], m["dst_blocks"])
            if i in self._handoff_ready:
                self._handoff_ready.remove(i)
        super()._release_slot(i)

    # -- prefill-pool stepping --------------------------------------------
    def _chunk_step(self) -> bool:
        """One chunk-prefill-only step over every mid-prefill slot (the
        prefill pool's whole turn; assembly mirrors ``_mixed_step`` minus
        the decode half)."""
        C = self.chunk
        slots_p = self._prefilling()
        if not slots_p:
            return False
        tokens = np.full((self.B, C), self.pad_id, np.int32)
        admit = np.zeros((self.B,), bool)
        first = np.zeros((self.B,), bool)
        clens = np.ones((self.B,), np.int32)
        starts = np.zeros((self.B,), np.int32)
        totals = np.ones((self.B,), np.int32)
        emits = []
        for i in slots_p:
            s = self.slots[i]
            off = s.chunk_next
            plen = len(s.req.prompt)
            nc = min(C, plen - off)
            tokens[i, :nc] = s.req.prompt[off:off + nc]
            admit[i] = True
            first[i] = not s.chunk_started
            clens[i] = nc
            starts[i] = off
            totals[i] = off + nc
            if off + nc == plen:
                emits.append(i)
        bt_w = np.where(admit[:, None], self.bt,
                        kvcache.NULL_BLOCK).astype(np.int32)
        ptok, self.caches = self._dispatch(
            "chunk", lambda: self.engine.chunk_slots_paged(
                self.caches, tokens, admit, first, clens, starts, totals,
                bt_w, self._next_rng()),
            chunks=[(int(starts[i]), int(clens[i])) for i in slots_p])
        self._admission_mark = True       # this round carried prefill work
        for i in slots_p:
            s = self.slots[i]
            s.chunk_started = True
            s.chunk_next += int(clens[i])
            self.stats["prefill_tokens"] += int(clens[i])
            self.stats["prefill_chunks"] += 1
        self.stats["prefill_steps"] += 1
        self.stats["prefill_slot_busy"] += len(slots_p)
        self.stats["prefill_slot_total"] += len(self._pf_slots)
        self._post_chunks(slots_p)
        ptok = self._materialize(ptok)
        for i in emits:
            self._complete_prefill(i, int(ptok[i]))
        return True

    def _post_chunks(self, slots_p: List[int]) -> None:
        super()._post_chunks(slots_p)     # prefill-shard prefix publication
        # eager migration: completed full blocks start their copy at the
        # chunk boundary they publish at, overlapping migration with the
        # remaining prefill instead of paying the whole prompt at handoff
        for i in slots_p:
            s = self.slots[i]
            if s.chunk_next < len(s.req.prompt):
                self._enqueue_migration(i)

    def _complete_prefill(self, i: int, tok: int) -> None:
        """The slot's chunk completed its prompt: record the first emitted
        token (sampled by the chunk program) and stage the handoff."""
        if not 0 <= tok < self.vocab:
            self._quarantine_slot(
                i, "error", f"poisoned prefill token {tok}")
            return
        s = self.slots[i]
        r = s.req
        s.toks.append(tok)
        if self.on_token is not None:
            self.on_token(r.rid, tok)
        r.stats["ttft_s"] = time.monotonic() - r.submitted_at
        r.stats["prefill_s"] = r.stats["ttft_s"] - r.stats["queue_s"]
        self.stats["emitted"] += 1
        if r.max_new <= 1 or (r.eos_id is not None and tok == r.eos_id):
            # nothing left to decode: complete off the prefill pool (the
            # retire path releases blocks + any eagerly-queued migration)
            self.dones[i] = True
            self.remaining[i] = 0
            s.chunk_next = None
            return
        m = self._mig.get(i)
        if m is None:
            m = self._mig[i] = {"dst": self._pick_decode_shard(),
                                "dst_blocks": [], "sent": 0, "ready_t": None}
        m["ready_t"] = time.monotonic()
        self._handoff_ready.append(i)
        # chunk_next stays == plen: the sentinel keeping _retire and
        # _prefilling off the slot while its blocks stream out

    # -- migration pipeline ------------------------------------------------
    def _enqueue_migration(self, i: int, final: bool = False) -> None:
        """Queue copies for slot ``i``'s blocks up to its published
        frontier (all of them incl. the partial tail when ``final``).  A
        decode-side prefix hit references the resident block instead of
        copying; destination exhaustion preempts the youngest decode-pool
        request once, then defers (retried every round)."""
        s = self.slots[i]
        prompt = s.req.prompt
        plen = len(prompt)
        done_toks = plen if s.chunk_next is None else min(s.chunk_next, plen)
        target = -(-plen // self.bs) if final else done_toks // self.bs
        if target == 0:
            return
        m = self._mig.get(i)
        if m is None:
            m = self._mig[i] = {"dst": self._pick_decode_shard(),
                                "dst_blocks": [], "sent": 0, "ready_t": None}
        dshard = m["dst"]
        src_shard = self._shard_of(i)
        hits: List[int] = []
        if self.prefix_cache:
            hits, _ = self.alloc.match_prefix(dshard, prompt)
        while m["sent"] < target:
            j = m["sent"]
            if j < len(hits):
                # the chain-verified block already lives in the decode
                # pool: hand the refcount off, skip the copy entirely
                self.alloc.incref(dshard, [hits[j]])
                m["dst_blocks"].append(hits[j])
                self.stats["migration_skipped_blocks"] += 1
            else:
                got = self.alloc.alloc(dshard, 1)
                if got is None and self._preempt_youngest(dshard):
                    got = self.alloc.alloc(dshard, 1)
                if got is None:
                    self.stats["migration_deferrals"] += 1
                    return
                src_local = self.slot_blocks[i][j]
                self.alloc.begin_migration(src_shard, [src_local])
                self._mig_queue.append((i, src_shard, src_local,
                                        dshard, got[0]))
                m["dst_blocks"].append(got[0])
            m["sent"] += 1

    def _advance_handoffs(self) -> None:
        """Finish staging prefill-complete slots: once every block (incl.
        the tail) is enqueued or referenced, free the prefill slot (the
        allocator pins keep queued sources alive until the copy executes)
        and move the request to the landing list."""
        for i in list(self._handoff_ready):
            s = self.slots[i]
            try:
                if self.faults:
                    self.faults.on_handoff()
                self._enqueue_migration(i, final=True)
            except MigrationFault as e:
                # failed mid-handoff: roll the whole handoff back (queued
                # copies unpinned, dst blocks freed — _release_slot) and
                # quarantine the request; nothing reached the decode pool
                self.stats["migration_faults"] += 1
                self._quarantine_slot(i, "error", str(e))
                continue
            m = self._mig[i]
            if m["sent"] < -(-len(s.req.prompt) // self.bs):
                continue                   # starved for dst blocks; retry
            self._handoff_ready.remove(i)
            m = self._mig.pop(i)
            self._landing.append({
                "req": s.req, "shard": m["dst"], "blocks": m["dst_blocks"],
                "toks": list(s.toks), "ready_t": m["ready_t"],
            })
            self.stats["handoffs"] += 1
            self._release_slot(i)          # _mig popped -> src blocks free
            self.slots[i] = _Slot()
            self.dones[i] = True
            self.remaining[i] = 0

    def _run_migrations(self) -> None:
        """Land waiting requests into free decode slots and execute every
        queued copy in ONE batched jitted step (global block ids; cross-
        shard pairs lower to the actual device-to-device transfer)."""
        if self._pipeline and (self._landing or self._mig_queue):
            # landing a request rewrites its decode slot's host state row —
            # exact values must merge before the next overlapped dispatch
            self._drain_pipeline()
        land = np.zeros((self.B,), bool)
        totals = np.zeros((self.B,), np.int32)
        landed = []
        for rec in self._landing:
            lo = rec["shard"] * self._spss
            slot = next((j for j in range(lo, lo + self._spss)
                         if self.slots[j].req is None and not land[j]), None)
            if slot is None:
                continue                   # decode pool full; lands later
            r = rec["req"]
            plen = len(r.prompt)
            land[slot] = True
            totals[slot] = plen
            s = _Slot(req=r, admitted_step=self.step_count)
            s.toks = list(rec["toks"])
            self.slots[slot] = s
            self._forced_done[slot] = False
            self.slot_blocks[slot] = list(rec["blocks"])
            self.bt[slot, :] = kvcache.NULL_BLOCK
            self.bt[slot, :len(rec["blocks"])] = rec["blocks"]
            t = int(rec["toks"][-1])
            self.tok[slot] = t
            self.pos[slot] = plen
            self.remaining[slot] = r.max_new - 1
            self.eos[slot] = -1 if r.eos_id is None else r.eos_id
            self.dones[slot] = r.eos_id is not None and t == r.eos_id
            wait = time.monotonic() - rec["ready_t"]
            r.stats["migration_wait_s"] = wait
            self._mig_wait.append(wait)
            if self.prefix_cache:
                self.alloc.register_prefix(rec["shard"], r.prompt,
                                           rec["blocks"][:plen // self.bs])
            landed.append(rec)
        for rec in landed:
            self._landing.remove(rec)
        if not self._mig_queue and not landed:
            return
        per = self.alloc.blocks_per_shard
        src = [sh * per + b for _, sh, b, _, _ in self._mig_queue]
        dst = [sh * per + b for _, _, _, sh, b in self._mig_queue]
        self.caches = self._dispatch(
            "migrate", lambda: self.engine.migrate_blocks(
                self.caches, src, dst, land, totals))
        for _, sh, b, _, _ in self._mig_queue:
            self.alloc.end_migration(sh, [b])
        n = len(self._mig_queue)
        self._mig_queue.clear()
        self.stats["migrated_blocks"] += n
        self.stats["migration_bytes"] += n * (self._block_bytes or 0)
        self.stats["migration_steps"] += 1
        self._note_usage()

    # -- failure isolation (migration-aware) --------------------------------
    def _finish_landing_record(self, rec: Dict, finish_reason: str,
                               error: Optional[str] = None) -> None:
        """Abort a fully-migrated request still waiting for a decode slot:
        free its destination blocks and retire it.  Callers must ensure the
        copy queue is EMPTY first — a queued batched copy still targets
        these blocks, and freeing them mid-queue would let the copy write
        into storage another request may have claimed."""
        assert not self._mig_queue, "landing abort with copies in flight"
        self._landing.remove(rec)
        self.alloc.free(rec["shard"], rec["blocks"])
        r = rec["req"]
        r.output = np.asarray(rec["toks"], np.int32)
        r.finish_reason = finish_reason
        if error is not None:
            r.stats["error"] = error
        r.stats.update({"emitted": len(rec["toks"]),
                        "finished_at": time.monotonic()})
        self.stats["timeouts" if finish_reason == "timeout"
                   else "quarantined"] += 1
        self._note_usage()
        self._finish(r)

    def _abort_stuck_entity(self) -> bool:
        """Last-resort livelock escape: abort ONE stuck request so every
        other stream keeps its slot.  Deterministic priority: a slot wedged
        mid-handoff, then a landed-but-unplaced request (only once the copy
        queue is drained — see ``_finish_landing_record``), then a
        mid-prefill slot."""
        victim = False
        if self._handoff_ready:
            self._quarantine_slot(self._handoff_ready[0], "error",
                                  "livelock: migration handoff stuck")
            victim = True
        elif self._landing and not self._mig_queue:
            self._finish_landing_record(
                self._landing[0], "error",
                "livelock: no decode slot ever freed for landing")
            victim = True
        else:
            for i, s in enumerate(self.slots):
                if s.req is not None and s.chunk_next is not None:
                    self._quarantine_slot(i, "error",
                                          "livelock: prefill stuck")
                    victim = True
                    break
        if victim:
            self.stats["livelock_aborts"] += 1
        return victim

    def _expire_deadlines(self) -> None:
        super()._expire_deadlines()
        # landed-but-unplaced requests hold destination blocks while they
        # wait for a decode slot — they time out too, but only once the
        # copy queue is empty (it drains every round via _run_migrations)
        if not self._landing or self._mig_queue:
            return
        now = time.monotonic()
        for rec in [rec for rec in self._landing
                    if rec["req"].deadline_s is not None
                    and now - rec["req"].submitted_at
                    >= rec["req"].deadline_s]:
            self._finish_landing_record(rec, "timeout")

    # -- decode-pool stepping ----------------------------------------------
    def _run_decode(self, n: int):
        # unlike the unified engine, decode here runs WHILE other slots are
        # mid-prefill: those rows carry stale positions but real tables, so
        # their frozen row-local rewrite must sink into the null block (the
        # _run_verify idiom) or it would clobber a freshly-written chunk.
        # _last_step_t stamps HERE so the ITL sample is the decode
        # dispatch's own duration (see class docstring).  Under overlap the
        # predicted-active mask is a SUPERSET of the device's true active
        # set (EOS surprises freeze rows early) — keeping a frozen row's
        # real table is safe: its row-local rewrite lands at its own valid
        # next position, which nothing reads.
        self._last_step_t = time.monotonic()
        active = (~self.dones) & (self.remaining > 0)
        bt = np.where(active[:, None], self.bt,
                      kvcache.NULL_BLOCK).astype(np.int32)
        tok, pos, dones, remaining = self._decode_inputs()
        return self.engine.decode_slots_paged(
            self.caches, tok, pos, dones, remaining,
            self.eos, bt, self._next_rng(), n=n)

    def _run_verify(self, vtok):
        self._last_step_t = time.monotonic()
        return super()._run_verify(vtok)

    # -- reporting ---------------------------------------------------------
    def request_summary(self) -> Dict:
        out = super().request_summary()
        st = self.stats
        pools: Dict = {
            "prefill_shards": len(self._pf_shards),
            "decode_shards": len(self._dec_shards),
            "prefill_steps": st["prefill_steps"],
            "decode_steps": st["decode_steps"],
            "prefill_occupancy": (
                st["prefill_slot_busy"] / st["prefill_slot_total"]
                if st["prefill_slot_total"] else 0.0),
            "migrated_blocks": st["migrated_blocks"],
            "migration_bytes": st["migration_bytes"],
            "migration_skipped_blocks": st["migration_skipped_blocks"],
            "migration_deferrals": st["migration_deferrals"],
            "handoffs": st["handoffs"],
        }
        w = percentile_summary(self._mig_wait)
        if w is not None:
            pools["migration_wait_s"] = w
        if "decode_itl_s" in out:
            pools["decode_itl_s"] = out["decode_itl_s"]
        out["pools"] = pools
        return out

    # -- main loop ---------------------------------------------------------
    def _serve_round(self) -> bool:
        """One disagg round.  Under overlap, only decode-pool blocks
        pipeline; any round that must land a migrated request or hand off
        blocks (host-exact slot arming) drains first.  Chunk-prefill steps
        do NOT force a drain — the chunk program reads only the (chained)
        cache future, and its own ``ptok`` materialization serializes after
        the in-flight decode blocks device-side anyway."""
        if self._block_bytes is None:
            from repro.models import transformer as tfm
            self._block_bytes = kvcache.pool_block_bytes(
                self.caches, tfm.build_groups(self.engine.cfg))
        self._round_prologue()
        if self._pipeline and (self._handoff_ready or self._landing
                               or self._mig_queue):
            # a migration landing rewrites a decode slot's position row on
            # the host — exact state must merge before the next dispatch
            self._drain_pipeline()
        self._retire_and_admit()
        did_prefill = bool(self._try_step(self._chunk_step))
        self._advance_handoffs()
        self._run_migrations()
        n = self._block_size()
        if n:
            if self.spec_k and not self._spec_suspended():
                self._drain_pipeline()
                self._try_step(self._spec_step)
            elif self.overlap:
                self._try_step(lambda: self._overlap_turn(n))
            else:
                self._try_step(lambda: self._decode_block(n))
        elif did_prefill:
            # prefill-only round: the virtual arrival clock advances so
            # arrivals keyed to decode steps stay admissible
            self.step_count += 1
        elif self._pipeline:
            self._land_next()
        busy = any(s.req is not None for s in self.slots)
        if (not busy and not self._landing and not self._mig_queue
                and not self._pipeline):
            pending = [r.arrival_step for r in self.queue]
            if not pending:
                return False
            self.step_count = max(self.step_count, min(pending))
            return True
        # livelock breaker: a full round with zero observable progress
        # (deferred migrations against a wedged decode pool) preempts
        # its way out rather than spinning forever
        sig = (len(self.done), self.stats["emitted"],
               self.stats["migrated_blocks"], self.stats["handoffs"],
               self.stats["prefill_chunks"], self.stats["decode_steps"],
               len(self.queue), len(self._landing))
        if sig == self._stall_sig:
            self._stall += 1
            if self._stall > 4 * self.B + 16:
                self._drain_pipeline()
                if not any(self._preempt_youngest(sh) for sh in
                           (*self._dec_shards, *self._pf_shards)):
                    # nothing preemptible either: abort ONE stuck request
                    # (loud counter) instead of killing the serve loop —
                    # the remaining streams get another full stall window
                    if not self._abort_stuck_entity():
                        raise RuntimeError(
                            "disagg scheduler stalled: no progress and "
                            "nothing to preempt or abort")
                self._stall = 0
        else:
            self._stall, self._stall_sig = 0, sig
        return True
