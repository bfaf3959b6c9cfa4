"""Continuous-batching scheduler: admission queue + slot-pool decode loop.

The scheduler turns the serve engine's request stream into a small,
fixed set of jit-stable programs.  One :class:`~repro.serve.slots.SlotPool`
holds ``n_slots`` persistent lanes; the loop is::

    while queue or active lanes:
        admit:   every placeable queued request claims a lane
        prefill: (chunked mode) ONE prefill_chunk dispatch advances every
                 prefilling lane by up to C prompt tokens
        decode:  ONE pooled decode step over all n_slots lanes, driven by
                 the per-slot position vector and the ``act`` phase mask
        sample:  per-lane greedy/temperature on the pooled logits
        evict:   lanes that hit max_new stream a Result out and free up —
                 the next admission joins mid-flight

Two prefill styles:

* **Legacy (default)**: admission runs a batch-1 prefill jitted per
  prompt length and scatters the fragment into the lane — simple, exact,
  but the compiled set grows with the number of distinct prompt lengths
  and every admission stalls the live decode lanes behind it.  Kept as
  the reference oracle.
* **Chunked** (``SchedulerPolicy(chunked_prefill=True)``): admission is a
  fused multi-admit — every placeable request claims a lane in one
  dispatch (one ``reset_recurrent_slots`` program; attention rows need
  no reset) — and prompts then stream through
  ``transformer.prefill_chunk`` in fixed-size chunks (pad-to-chunk, per
  lane ``start``/``n_valid``), interleaved with pooled decode steps: a
  per-lane phase keeps decoding lanes emitting tokens while prefilling
  lanes advance through their prompts, so a long prompt never
  head-of-line blocks live lanes.  The prefill compiled set is O(#chunk
  sizes), independent of the workload's prompt-length mix.

Because the decode step's shapes never depend on the arrival pattern
(always ``tok (n_slots, 1)``, ``pos (n_slots,)``, ``act (n_slots,)``),
exactly one decode program is compiled no matter how requests arrive.

**Paged KV** (``SchedulerPolicy(paged=True)``, requires chunked
prefill): the pool's attention caches become a global block pool + per
lane block tables (see ``serve.slots``).  The scheduler's extra duties
are small and host-side: admission checks *block* capacity on top of
free lanes (first-chunk demand against free blocks, worst-case lifetime
demand against uncommitted capacity — the latter makes on-demand growth
infallible, so a lane can never stall mid-decode), each prefill chunk
and each decode step grant the blocks their writes are about to land in
(``SlotPool.grow_rows``), and eviction returns blocks to the free list.
The block table rides through both jitted programs as a replicated
(n_slots, blocks_per_lane) operand — shapes are static, so the
one-decode-program property is untouched.

Admission policy (:class:`SchedulerPolicy`): FIFO order within an SLO
tier — ``latency``-tier requests outrank ``throughput``-tier ones, and
anti-starvation aging promotes any request that has waited
``aging_steps`` scheduler steps — with optional max-wait batching: hold
admissions until ``min_admit`` requests can be placed together or the
oldest has waited ``max_wait`` scheduler steps, amortising prefill
dispatches under bursty arrivals.  Per-request ``temperature`` /
``max_new`` / ``tier`` ride in the Request, as in the bucketed engine.

**Overcommit + preemption** (``SchedulerPolicy(overcommit > 1.0)``,
requires paged): admission stops gating on worst-case lifetime blocks
against the *physical* pool and instead reserves against
``BlockAllocator.commit_capacity = shard_blocks * overcommit`` — most
requests finish early, so the pool serves more concurrent lanes than
worst-case accounting would allow.  The price is that on-demand growth
can now exhaust a shard; before every grow the scheduler runs
``_ensure_headroom``, which preempts victim lanes (lowest priority
first: throughput tier before latency, then most recently admitted)
until the step's block demand fits.  Preemption is a *recompute swap*:
the victim's blocks are freed, its generated-so-far tokens are
snapshotted, and the request re-enters the queue with prompt +
generated as its new prompt — re-prefill through the exact chunked
path reconstructs identical KV/recurrent state, so greedy output stays
token-identical to the no-preemption oracle.  Two rules make this
deadlock-free: requests whose worst case exceeds one shard's physical
blocks are rejected up front (unchanged from overcommit=1.0), so a
lane alone in its shard can always grow; and admission still gates the
first chunk's demand against free blocks, so a fresh admit always
makes progress before it can be chosen as a victim.

**Bit-plane speculative decoding** (``SchedulerPolicy(spec_decode=True)``,
requires paged): decode lanes self-draft from truncated bit planes of
the SAME PackedWeights — no second model.  Each round chains up to
``gamma`` async dispatches of ONE jitted draft step (``_spec_draft_fn``:
a pooled decode step traced under ``models.common.active_plane_count``
with ``draft_planes`` as a *runtime* operand and a donated cache, so
the chain reuses buffers in place with no host sync between steps),
then one full-precision ``prefill_chunk`` with ``return_all_logits``
scoring every drafted position at once (``_spec_verify_fn``, fixed
chunk width ``gamma`` with ``nval`` masking shallower rounds) — two
compiled programs total, regardless of round depth or precision level.  The longest draft prefix matching the verify argmax
commits (plus the verify's correction token on a rejection — so every
round commits >= 1 token per lane), the verify's KV writes overwrite
every draft-precision row, and rejected rows rewind by a position
decrement plus tail-block free (``SlotPool.commit_spec`` — no data
movement).  Greedy verify makes the output token-identical to
non-speculative decode; per-lane draft depth backs off on rejections
(``SlotState.spec_gamma``).  Preemption can only fire at round setup,
so a preempted lane's snapshot never contains an unverified draft.

**Precision tiers + load-triggered degrade**
(``SchedulerPolicy(precision_tiers={...})`` / ``degrade=True``, packed
models with chunked prefill): BSQ's packed planes make serving
precision a per-step runtime knob, and this layer is the policy on top.
``Request.precision`` names a class ("full", a tier-table key, or an
explicit plane count — validated like ``Request.tier``); prefill always
runs at full precision, and each decode step groups its lanes by
effective plane count and runs one pooled dispatch per distinct count
(``plane_grouping=False``: one dispatch at the max) — the plane count
is a traced operand of the SAME single compiled decode program, exactly
like the spec draft step.  With ``degrade=True`` the scheduler sheds
one plane per pressured step (queue depth / occupancy / windowed
preemption rate past the policy thresholds) from every tier, clamped at
per-class floors, and restores one per ``degrade_hysteresis`` calm
steps — load sheds *precision* instead of requests.  Every emitted
token's plane count is logged (``SlotState.plane_log`` ->
``Result.plane_log``), and because the runtime plane dispatch is
bitwise-equal to static truncation, each token is identical to the
static-truncation oracle at its logged count — the conformance
harness's invariant for mid-stream switches.

Time is measured in scheduler steps (one pooled decode = one step);
arrival times for simulated workloads are expressed on that clock.

**Observability**: the scheduler emits through the engine's
:class:`repro.obs.Observability` bundle instead of ad-hoc lists.  Every
request gets a trace span (``enqueued -> admitted(slot[, blocks]) ->
prefill_chunk* -> first_token -> decode_step* ->
finished|abandoned|evicted``) in the flight recorder, and the per-step
telemetry lands in bounded-reservoir histograms
(``serve_occupancy`` / ``serve_decode_step_ms`` / ``serve_ttft_ms`` /
the paged block gauges — capacity ``SchedulerPolicy.telemetry_capacity``)
so a long-lived server holds O(capacity) memory.  ``Result.prefill_ms``
reports TTFT as defined by :meth:`repro.obs.trace.RequestTrace.ttft_ms`
— the ``admitted`` event (the wall clock at the admission burst that
dequeued the request, so legacy admission includes the serialisation
behind earlier batch-1 prefills in the same burst — exactly the cost
multi-admit removes) to the ``first_token`` event.  The metric
catalogue and span schema live in docs/observability.md.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Deque, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..dist import sharding as dist_sharding
from ..models import transformer
from ..models.common import (active_plane_count, packed_shard_mesh,
                             paged_shard_mesh)
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .slots import SlotPool, SlotState, reset_recurrent_slots, scatter_slot


@dataclasses.dataclass
class SchedulerPolicy:
    """Admission knobs.  Defaults: admit greedily, legacy batch-1 prefill."""

    n_slots: int = 8
    min_admit: int = 1  # batch admissions until this many can go together
    max_wait: int = 0  # ...but never hold the oldest more than this many steps
    chunked_prefill: bool = False  # prompts stream through the pooled program
    # Fixed chunk sizes (pad-to-chunk): each prefill dispatch picks the
    # smallest size covering the longest remaining prompt (or the largest
    # size).  The compiled prefill set is bounded by len(chunk_sizes).
    chunk_sizes: Tuple[int, ...] = (128, 32, 1)
    # Paged KV: the pool's attention caches become a global pool of
    # fixed-size blocks + per-lane block tables (serve/slots.py) so cache
    # HBM scales with live tokens, not n_slots * max_len.  block_size
    # should divide (or be divided by) the chunk sizes so chunk
    # boundaries land on block boundaries; n_blocks=None sizes the pool
    # to the unpaged capacity (callers shrink it to actually save HBM).
    paged: bool = False
    block_size: int = 32
    n_blocks: Optional[int] = None
    # Decode reads walk the block table in place via the Pallas paged
    # attention kernel (kernels/paged_attention.py) instead of gathering
    # each lane's full pool view — per-step attention HBM reads scale
    # with live tokens.  The gather path stays the conformance reference.
    paged_kernel: bool = False
    # Optimistic overcommit (paged only): admit against
    # shard_blocks * overcommit commitment capacity instead of the
    # physical pool.  1.0 (default) is exact worst-case gating — growth
    # can never fail and the preemption path is provably unreachable.
    # Past 1.0 the scheduler preempts victim lanes (recompute swap) when
    # a step's block demand would exhaust a shard.
    overcommit: float = 1.0
    # Anti-starvation aging: a queued request that has waited this many
    # scheduler steps is promoted to the latency class for admission
    # ordering, so throughput-tier work cannot starve behind a stream of
    # latency-tier arrivals.
    aging_steps: int = 64
    # Occupancy-aware chunk sizing: scale the prefill chunk down as more
    # lanes are decoding (small chunks keep per-step latency low for live
    # decode lanes; large chunks drain prompts fast when the pool is
    # idle).  Picked sizes always come from chunk_sizes, so the compiled
    # prefill set stays bounded.  False restores the static
    # smallest-covering-chunk rule.
    occupancy_chunking: bool = True
    # Bit-plane speculative decoding (paged only): decode lanes
    # self-draft up to ``gamma`` pooled steps per round with only the
    # ``draft_planes`` most significant bit planes of every PackedWeight
    # active (a RUNTIME operand of the same compiled programs — see
    # models.common.active_plane_count), then ONE full-precision
    # chunked-prefill-style verify pass scores every drafted position at
    # once.  Greedy verify makes the output token-identical to
    # non-speculative decode; rejected drafts rewind positions through
    # the block tables (no data movement).  Requires paged serving,
    # attention-only layer patterns and all-greedy requests.
    spec_decode: bool = False
    draft_planes: int = 2  # active bit planes during draft steps
    gamma: int = 4  # max draft steps per round (per-lane depth backs off)
    # Serve-time precision tiers (packed models, chunked prefill): maps a
    # precision-class name (what ``Request.precision`` carries) to an
    # active bit-plane count, e.g. {"economy": 3} — pick the counts from
    # quality-probe data (obs.quality.precision_tiers_from_probe).  The
    # class "full" is implicit (= the model's n_bits) and cannot be
    # remapped.  None (default) disables tier resolution entirely: every
    # request must be precision "full" and the untiered decode program
    # is compiled, exactly as before.  Prefill always runs at full
    # precision (the first token is full quality; truncated KV never
    # poisons a lane's prompt rows); only decode steps run tiered.
    precision_tiers: Optional[Dict[str, int]] = None
    # Group each step's decode lanes by effective plane count and run one
    # pooled dispatch per distinct count (every group pays only its own
    # planes; still ONE compiled program — the count is a runtime
    # operand).  Off: one dispatch at the max count across live decode
    # lanes serves every lane (fewer dispatches, no compute savings; the
    # max IS the plane count logged for every token that step).
    plane_grouping: bool = True
    # Load-triggered degrade (tiered engines): when queue depth /
    # occupancy / preemption rate cross the thresholds below, shed one
    # active plane per pressured step — from EVERY tier, clamped at each
    # class's floor — instead of shedding requests; restore one plane per
    # ``degrade_hysteresis`` consecutive calm steps.  Every transition
    # records a trace span on each live lane plus
    # serve_degrade_events_total{direction} / serve_active_planes{tier}.
    degrade: bool = False
    degrade_queue_depth: int = 2  # queued requests that count as pressure
    degrade_occupancy: float = 1.0  # lane-occupancy fraction that counts as pressure (with a non-empty queue)
    degrade_preempt_rate: float = 0.5  # preemptions/step over the window that count as pressure
    degrade_window: int = 16  # steps of preemption history in the rate
    degrade_hysteresis: int = 4  # calm steps required per restored plane
    # Per-class plane floor the degrade loop may not shed below (default
    # 1 for every class; with spec_decode the effective floor is raised
    # to draft_planes + 1 so a degraded verify always out-informs the
    # draft — see the degrade loop's clamp warning).
    precision_floors: Optional[Dict[str, int]] = None
    # Bounded-telemetry capacity: per-step observations (occupancy,
    # decode-step ms, block usage, ...) live in fixed-size reservoirs of
    # this many entries (obs.metrics.Histogram), so a long-lived server
    # holds O(capacity) telemetry memory.  The default comfortably holds
    # every bench/CI workload, so percentiles match the unbounded lists
    # this replaced bit-for-bit there.
    telemetry_capacity: int = obs_metrics.DEFAULT_HISTOGRAM_CAPACITY

    def __post_init__(self):
        if self.min_admit > 1 and self.max_wait <= 0:
            raise ValueError(
                "min_admit > 1 requires max_wait > 0 — with max_wait=0 the "
                "hold window is empty and min_admit would be silently inert"
            )
        if self.chunked_prefill and (
            not self.chunk_sizes or any(c < 1 for c in self.chunk_sizes)
        ):
            raise ValueError(
                f"chunk_sizes={self.chunk_sizes!r}: need at least one size >= 1"
            )
        if self.paged:
            if not self.chunked_prefill:
                raise ValueError(
                    "paged=True requires chunked_prefill=True — legacy batch-1 "
                    "admission scatters a contiguous lane row the block pool "
                    "does not have"
                )
            if self.block_size < 1:
                raise ValueError(f"block_size={self.block_size}: need >= 1")
            if self.n_blocks is not None and self.n_blocks < 1:
                raise ValueError(f"n_blocks={self.n_blocks}: need >= 1 (or None)")
        if self.paged_kernel and not self.paged:
            raise ValueError(
                "paged_kernel=True requires paged=True — the kernel walks the "
                "block table a dense cache does not have"
            )
        if self.overcommit < 1.0:
            raise ValueError(
                f"overcommit={self.overcommit}: factors below 1.0 would "
                "strand physical blocks behind the commitment gate"
            )
        if self.overcommit > 1.0 and not self.paged:
            raise ValueError(
                "overcommit > 1.0 requires paged=True — only the block pool "
                "has the commitment accounting (and the preemption escape "
                "hatch) overcommit relies on"
            )
        if self.aging_steps < 1:
            raise ValueError(
                f"aging_steps={self.aging_steps}: need >= 1 (aging at 0 "
                "steps would flatten the tier ordering entirely)"
            )
        if self.spec_decode:
            if not self.paged:
                raise ValueError(
                    "spec_decode=True requires paged=True — the draft/verify "
                    "rewind frees rejected rows through the block tables, "
                    "which a dense per-lane cache does not have"
                )
            if self.draft_planes < 1:
                raise ValueError(
                    f"draft_planes={self.draft_planes}: need >= 1 (zero "
                    "active planes is not a model)"
                )
            if self.gamma < 1:
                raise ValueError(
                    f"gamma={self.gamma}: need >= 1 draft step per round"
                )
        if self.precision_tiers is not None:
            if not self.chunked_prefill:
                raise ValueError(
                    "precision_tiers requires chunked_prefill=True — legacy "
                    "batch-1 admission is the full-precision reference oracle "
                    "and does not carry per-lane plane bookkeeping"
                )
            for name, k in self.precision_tiers.items():
                if name == "full":
                    raise ValueError(
                        "precision_tiers must not remap 'full' — it is "
                        "implicitly the model's n_bits"
                    )
                if not isinstance(k, int) or k < 1:
                    raise ValueError(
                        f"precision tier {name!r}: plane count {k!r} must be "
                        "an int >= 1"
                    )
                if self.spec_decode and k <= self.draft_planes:
                    # A tier at or below the draft precision makes the
                    # verify dispatch carry zero information (draft ==
                    # verify model) — reject here rather than burn it.
                    raise ValueError(
                        f"precision tier {name!r}: {k} planes <= "
                        f"draft_planes={self.draft_planes} — the effective "
                        "serving precision must be strictly above the draft "
                        "precision for the verify to add information"
                    )
        if self.precision_floors is not None:
            if self.precision_tiers is None and not self.degrade:
                raise ValueError(
                    "precision_floors without precision_tiers or degrade "
                    "would be silently inert"
                )
            for name, fl in self.precision_floors.items():
                if not isinstance(fl, int) or fl < 1:
                    raise ValueError(
                        f"precision floor {name!r}: {fl!r} must be an int >= 1"
                    )
        if self.degrade:
            if not self.chunked_prefill:
                raise ValueError(
                    "degrade=True requires chunked_prefill=True (same "
                    "per-lane plane bookkeeping as precision_tiers)"
                )
            if self.degrade_queue_depth < 1:
                raise ValueError(
                    f"degrade_queue_depth={self.degrade_queue_depth}: need "
                    ">= 1 (depth 0 would mean permanent pressure)"
                )
            if not 0.0 < self.degrade_occupancy <= 1.0:
                raise ValueError(
                    f"degrade_occupancy={self.degrade_occupancy}: need a "
                    "fraction in (0, 1]"
                )
            if self.degrade_preempt_rate < 0.0:
                raise ValueError(
                    f"degrade_preempt_rate={self.degrade_preempt_rate}: "
                    "need >= 0"
                )
            if self.degrade_window < 1:
                raise ValueError(
                    f"degrade_window={self.degrade_window}: need >= 1 step")
            if self.degrade_hysteresis < 1:
                raise ValueError(
                    f"degrade_hysteresis={self.degrade_hysteresis}: need "
                    ">= 1 calm step per restored plane"
                )


@dataclasses.dataclass
class _Pending:
    request: "repro.serve.engine.Request"  # noqa: F821 — engine imports us
    arrival: int
    enqueued_at: Optional[int] = None  # step it became visible to admission
    seq: int = 0  # global FIFO sequence; stable across preemption requeues
    # Recompute-swap resume state: the tokens a preempted run had already
    # generated.  The effective prompt is the original prompt extended by
    # these (re-prefill recomputes their KV rows exactly), the effective
    # max_new shrinks by their count, and the Result stitches them back.
    prior: Optional[List[int]] = None
    # Tiered engines: plane counts the ``prior`` tokens were emitted at
    # (parallel list) — the Result's plane_log stitches them back too.
    prior_planes: Optional[List[int]] = None

    @property
    def prompt_len(self) -> int:
        return len(self.request.tokens) + len(self.prior or ())

    def prompt_tokens(self) -> np.ndarray:
        toks = np.asarray(self.request.tokens, np.int32)
        if self.prior:
            toks = np.concatenate([toks, np.asarray(self.prior, np.int32)])
        return toks

    @property
    def max_new(self) -> int:
        return self.request.max_new - len(self.prior or ())

    @property
    def tier(self) -> str:
        return getattr(self.request, "tier", "throughput")

    @property
    def precision(self):
        return getattr(self.request, "precision", "full")


def preemption_order(candidates: List[Tuple[int, "SlotState"]]  # noqa: F821
                     ) -> List[Tuple[int, "SlotState"]]:
    """Victim priority over ``(slot, SlotState)`` live-lane candidates:
    best victim FIRST.  Throughput-tier lanes go before latency-tier
    ones (a latency lane is never preempted while a throughput victim
    is available), most recently admitted first within a tier (LIFO —
    the youngest lane has the least recompute debt and the oldest makes
    progress, which is what guarantees the highest-priority lane always
    runs to completion), highest slot index as the deterministic
    tie-break.  Pure and host-side so the hypothesis harness can drive
    it against arbitrary interleavings without a model."""
    return sorted(
        candidates,
        key=lambda c: (c[1].tier == "latency", -c[1].admit_seq, -c[0]),
    )


class ContinuousScheduler:
    """Drives a ServeEngine's params/config through a slot-pool decode loop.

    The engine owns params, sampling and placement; the scheduler owns the
    pool, the queue and the jitted programs.  ``stream()`` yields Results
    as lanes finish (streaming completion); ``run()`` collects them.
    """

    def __init__(self, engine, policy: SchedulerPolicy):
        self.engine = engine
        self.policy = policy
        self.pool = SlotPool(
            engine.cfg, policy.n_slots, engine.max_len, mesh=engine.mesh,
            cache_dtype=jnp.dtype(engine.cfg.kv_cache_dtype),
            paged=policy.paged, block_size=policy.block_size,
            n_blocks=policy.n_blocks, overcommit=policy.overcommit,
            registry=engine.obs.registry,
        )
        cfg = engine.cfg
        # ONE pooled decode program: pos/act are (n_slots,) vectors, so the
        # compiled shape is independent of which lanes are live or what
        # phase they are in.  With a mesh, the output cache sharding is
        # constrained to the pool's shardings so the program's signature is
        # a fixed point — no sharding drift, no second compile.
        out_sh = None
        if engine.mesh is not None:
            out_sh = (None, self.pool.shardings["cache"])
        self._cache_out_sh = out_sh

        # Shard-local paged decode: when the block tables co-shard with
        # the pool over the data axes (table_shards > 1), the decode
        # trace runs paged attention inside shard_map over the engine
        # mesh — each shard touches only its own pool slice.
        self._paged_mesh = (
            engine.mesh
            if policy.paged and self.pool.table_shards > 1 else None
        )
        pk = policy.paged_kernel

        if policy.spec_decode:
            # Draft rows are rewound by decrementing positions and
            # freeing tail blocks — state that cannot be rewound that
            # way (sliding-window ring buffers wrap, recurrent state
            # integrates every token, MoE routing is fine but cross
            # attention reads per-request frontend embeddings the pooled
            # draft scan does not thread) is gated out up front.
            bad = [k for k in cfg.layer_pattern
                   if k.split("+")[0] != "attn" or "+" in k]
            if bad:
                raise ValueError(
                    f"spec_decode=True requires an attention-only layer "
                    f"pattern (rewind is a position decrement); got "
                    f"{cfg.layer_pattern!r} with non-rewindable kinds {bad!r}"
                )
            if cfg.n_experts:
                raise ValueError(
                    "spec_decode=True does not support MoE layers "
                    f"(n_experts={cfg.n_experts})"
                )

        # Precision tiers / degrade: resolve the tier table against the
        # model's packed bit width.  ``self._tiered`` gates everything —
        # an untiered scheduler compiles the exact decode program it
        # always did and carries zero per-lane plane bookkeeping.
        from ..core.packing import packed_leaves

        packed = packed_leaves(engine.params)
        self._n_bits: Optional[int] = (
            max(pw.n_bits for pw in packed) if packed else None)
        self._tiered = policy.precision_tiers is not None or policy.degrade
        if self._tiered:
            if self._n_bits is None:
                raise ValueError(
                    "precision_tiers/degrade need a packed model — float "
                    "params have no bit planes to shed"
                )
            self._tier_planes: Dict[str, int] = {"full": self._n_bits}
            for name, k in (policy.precision_tiers or {}).items():
                if k > self._n_bits:
                    raise ValueError(
                        f"precision tier {name!r}: {k} planes > the model's "
                        f"n_bits={self._n_bits}"
                    )
                self._tier_planes[name] = int(k)
            if policy.spec_decode and self._n_bits <= policy.draft_planes:
                raise ValueError(
                    f"draft_planes={policy.draft_planes} >= n_bits="
                    f"{self._n_bits} — no tier can serve strictly above the "
                    "draft precision"
                )
            self._floors: Dict[str, int] = dict(policy.precision_floors or {})
            # Max useful shed: past it every tier already sits at its
            # floor and further sheds are inert (and with spec_decode
            # would push a verify to draft precision — the clamp).
            self._shed_ceiling = max(
                k - self._floor(name) for name, k in self._tier_planes.items())
            self._shed_ceiling = max(self._shed_ceiling, 0)
        else:
            self._tier_planes = {}
            self._floors = {}
            self._shed_ceiling = 0
        # Degrade-loop state: planes currently shed (global, clamped at
        # each tier's floor), consecutive calm steps, and a bounded
        # window of per-step preemption counts for the rate trigger.
        self._shed = 0
        self._calm = 0
        self._preempt_step = 0
        self._preempt_window: Deque[int] = deque(
            maxlen=policy.degrade_window)
        self._degrade_warned = False
        # Deterministic test hook: when set, ``force_shed(step) -> int``
        # overrides the pressure triggers entirely (still floor-clamped)
        # — the conformance harness drives plane switches on an exact
        # schedule with it.  Requires policy.degrade=True.
        self.force_shed: Optional[Callable[[int], int]] = None
        self.degrade_sheds = 0
        self.degrade_restores = 0

        if self._tiered:
            # Same single pooled decode program, with the step's active
            # plane count as ONE extra traced int32 operand (the runtime
            # plane dispatch the spec draft step already uses) — tier
            # levels and degrade transitions never fork a compile.
            def _decode_fn(p, cache, tok, pos, act, table, planes):
                with packed_shard_mesh(engine._packed_mesh), \
                     paged_shard_mesh(self._paged_mesh):
                    with active_plane_count(planes):
                        return transformer.decode_step(
                            p, cache, tok, pos, cfg, active=act,
                            block_table=table, paged_kernel=pk)
        else:
            def _decode_fn(p, cache, tok, pos, act, table):
                with packed_shard_mesh(engine._packed_mesh), \
                     paged_shard_mesh(self._paged_mesh):
                    return transformer.decode_step(p, cache, tok, pos, cfg, active=act,
                                                   block_table=table, paged_kernel=pk)

        self._decode = jax.jit(_decode_fn, out_shardings=out_sh)
        self._prefill_cache: Dict[int, Callable] = {}  # legacy: per prompt length
        self._chunk_cache: Dict[int, Callable] = {}  # chunked: per chunk size
        # Spec decode: ONE draft-step program (round depth = dispatch
        # count, draft_planes a RUNTIME operand) plus ONE fixed-width
        # verify program — the set grows with neither gamma nor
        # precision levels.
        self._spec_draft_jit: Optional[Callable] = None
        self._spec_verify_jit: Optional[Callable] = None
        # Chunked multi-admit: ONE program for every burst size — the slot
        # vector is fixed-size (n_slots,), padded with the out-of-bounds
        # index n_slots whose writes drop.
        # A per-scheduler closure, not jit(reset_recurrent_slots) directly:
        # jitting the shared module function would pool the trace cache —
        # and compiled_admit_programs() telemetry — across every engine in
        # the process.
        def _reset_fn(cache, slots):
            return reset_recurrent_slots(cache, slots)

        self._reset_slots = jax.jit(
            _reset_fn,
            out_shardings=self.pool.shardings["cache"] if engine.mesh is not None else None,
        )
        # Chunk staging buffers are layout-decided by the dist layer like
        # every other tensor (replicated control vectors).
        self._chunk_shardings = None
        if engine.mesh is not None:
            specs = dist_sharding.chunk_buffer_specs(
                {"tok": 0, "start": 0, "nvalid": 0, "slots": 0}, engine.mesh
            )
            self._chunk_shardings = dist_sharding.tree_shardings(engine.mesh, specs)
        # Telemetry: bounded-reservoir histograms in the engine's obs
        # registry (scraped by launch.serve --metrics-port, snapshotted by
        # bench_serve).  The legacy trace attributes below alias the same
        # Histogram objects, so old call sites keep reading the numbers.
        self.obs = engine.obs
        reg = self.obs.registry
        tcap = policy.telemetry_capacity
        self._h_occ = reg.histogram(
            "serve_occupancy", "live decode lanes per pooled decode step",
            capacity=tcap)
        self._h_step = reg.histogram(
            "serve_decode_step_ms", "pooled decode step wall time (ms)",
            capacity=tcap)
        self._h_ttft = reg.histogram(
            "serve_ttft_ms",
            "time to first token (admitted -> first_token span, ms)",
            capacity=tcap)
        self._h_burst = reg.histogram(
            "serve_admit_burst", "requests admitted per admission burst",
            capacity=tcap)
        self._c_req = reg.counter(
            "serve_requests_total", "requests retired, by terminal outcome",
            labels=("outcome",))
        self._c_blocked = reg.counter(
            "serve_admit_blocked_total",
            "scheduler steps where a queued request could not be placed")
        self._c_chunks = reg.counter(
            "serve_prefill_chunks_total", "prefill_chunk dispatches")
        self._c_preempt = reg.counter(
            "serve_preemptions_total",
            "lanes preempted under overcommit pressure (blocks reclaimed, "
            "request re-queued for re-prefill), by SLO tier",
            labels=("tier",))
        self._c_preempt_rows = reg.counter(
            "serve_preempted_rows_total",
            "live KV cache rows discarded by preemption (recompute debt)")
        self._h_tier_ttft = reg.histogram(
            "serve_tier_ttft_ms",
            "time to first token by SLO tier (same span as serve_ttft_ms)",
            labels=("tier",), capacity=tcap)
        self._c_steps = reg.counter(
            "serve_decode_steps_total", "pooled decode step dispatches")
        # Speculative decoding: per-lane draft steps, accept/reject
        # outcomes of the full-precision verify, and the running
        # acceptance rate (accepted / drafted) as a gauge.
        self._c_spec_rounds = reg.counter(
            "serve_spec_rounds_total",
            "speculative draft+verify round dispatches")
        self._c_spec_draft = reg.counter(
            "serve_spec_draft_steps_total",
            "per-lane draft steps run at draft precision")
        self._c_spec_accept = reg.counter(
            "serve_spec_accept_total",
            "drafted tokens accepted by the full-precision verify")
        self._c_spec_reject = reg.counter(
            "serve_spec_reject_total",
            "drafted tokens rejected by the full-precision verify")
        self._g_spec_rate = reg.gauge(
            "serve_spec_accept_rate",
            "running draft acceptance rate (accepted / drafted)")
        self._g_queue = reg.gauge(
            "serve_queue_depth", "requests waiting for a lane")
        self._g_progs = reg.gauge(
            "serve_compiled_programs", "compiled XLA programs by stage",
            labels=("kind",))
        # Precision tiers / degrade loop: current effective plane count
        # per precision class, and shed/restore transition counts.
        self._g_active_planes = None
        self._c_degrade = None
        if self._tiered:
            self._g_active_planes = reg.gauge(
                "serve_active_planes",
                "effective active bit planes by precision tier "
                "(tier plane count minus the degrade loop's shed, "
                "clamped at the tier's floor)",
                labels=("tier",))
            self._c_degrade = reg.counter(
                "serve_degrade_events_total",
                "degrade-loop plane transitions, by direction "
                "(shed / restore)",
                labels=("direction",))
            self._set_plane_gauges()
        # paged telemetry: per decode step, pool blocks in use and live
        # cache rows (occupancy = used/n_blocks; fragmentation = wasted
        # tail rows of partially-filled blocks), and the blocks the
        # decode attention actually reads (the paged kernel's HBM
        # traffic; the gather path reads blocks_per_lane per live lane)
        self._h_blocks = reg.histogram(
            "serve_blocks_used", "pool blocks in use per decode step",
            capacity=tcap)
        self._h_rows = reg.histogram(
            "serve_live_rows", "live KV cache rows per decode step",
            capacity=tcap)
        self._h_frag = reg.histogram(
            "serve_fragmentation",
            "wasted fraction of allocated block rows per decode step",
            capacity=tcap)
        self._h_attn = reg.histogram(
            "serve_attn_read_blocks",
            "pool blocks read by decode attention per step", capacity=tcap)
        # Legacy names (bench/tests): the same bounded reservoirs.
        self.occupancy_trace = self._h_occ
        self.decode_ms_trace = self._h_step
        self.block_used_trace = self._h_blocks
        self.live_rows_trace = self._h_rows
        self.attn_read_blocks_trace = self._h_attn
        self.admit_bursts = obs_metrics.Ring(tcap)
        self.decode_ms_total = 0.0
        self.decode_steps = 0
        self.prefill_chunks = 0
        # Spec-decode scalar telemetry (bench/CI reads these directly).
        self.spec_rounds = 0
        self.spec_drafted = 0  # per-lane draft steps (drafted tokens)
        self.spec_accepted = 0  # drafted tokens the verify accepted
        self.spec_committed = 0  # tokens committed (accepts + corrections)
        # Overcommit bookkeeping: which _Pending occupies each lane (so a
        # preemption can rebuild the queue entry) and a monotone admission
        # counter driving the LIFO leg of preemption_order.
        self._lane_pend: Dict[int, _Pending] = {}
        self._admit_seq = 0

    # -- jitted programs ---------------------------------------------------
    def _prefill_fn(self, plen: int) -> Callable:
        """Batch-1 prefill + scatter-into-lane, jitted per prompt length.
        The lane index is a traced operand, so all lanes share the program."""
        fn = self._prefill_cache.get(plen)
        if fn is None:
            engine = self.engine

            def prefill_into_slot(params, pool_cache, tokens, slot):
                with packed_shard_mesh(engine._packed_mesh):
                    logits, part = transformer.prefill(
                        params, {"tokens": tokens}, engine.cfg, engine.max_len,
                        cache_dtype=self.pool.cache_dtype,
                    )
                return logits, scatter_slot(pool_cache, part, slot)

            fn = jax.jit(prefill_into_slot, out_shardings=self._cache_out_sh)
            self._prefill_cache[plen] = fn
        return fn

    def _chunk_fn(self, chunk: int) -> Callable:
        """Pooled prefill-chunk program, jitted per chunk size."""
        fn = self._chunk_cache.get(chunk)
        if fn is None:
            engine = self.engine

            def chunk_into_pool(params, pool_cache, toks, start, nvalid, table):
                with packed_shard_mesh(engine._packed_mesh):
                    return transformer.prefill_chunk(
                        params, pool_cache, toks, start, nvalid, engine.cfg,
                        cache_dtype=self.pool.cache_dtype, block_table=table,
                    )

            fn = jax.jit(chunk_into_pool, out_shardings=self._cache_out_sh)
            self._chunk_cache[chunk] = fn
        return fn

    def _spec_draft_fn(self) -> Callable:
        """ONE jitted draft step shared by every round: a pooled
        ``decode_step`` traced under ``active_plane_count`` (greedy
        argmax feeds each dispatch's token into the next through the
        on-device ``tok``/``pos`` carry — no host sync between steps),
        with the per-step ``act`` row freezing lanes whose depth or
        phase excludes them.  Round depth is just the number of
        dispatches, so no program is compiled per ``gamma``; ``planes``
        is a TRACED int32 operand, so no program is compiled per
        precision level either — the kernel-level runtime-active-plane
        dispatch surfacing at the scheduler.  The cache operand is
        DONATED: each step overwrites the previous step's buffers in
        place instead of allocating a fresh pool, which is most of the
        per-step win over a fused ``lax.scan`` (whose carry defeats
        buffer reuse)."""
        fn = self._spec_draft_jit
        if fn is None:
            engine = self.engine
            cfg = engine.cfg
            pk = self.policy.paged_kernel
            V = cfg.vocab_size

            def draft_step(p, cache, tok, pos, act, table, planes):
                with packed_shard_mesh(engine._packed_mesh), \
                     paged_shard_mesh(self._paged_mesh):
                    with active_plane_count(planes):
                        logits, cache = transformer.decode_step(
                            p, cache, tok, pos, cfg, active=act,
                            block_table=table, paged_kernel=pk)
                    nxt = jnp.argmax(logits[:, :V], axis=-1).astype(jnp.int32)
                    tok = jnp.where(act[:, None], nxt[:, None], tok)
                    pos = pos + act.astype(jnp.int32)
                return cache, tok, pos, nxt

            out_sh = None
            if engine.mesh is not None:
                sh = self.pool.shardings
                out_sh = (sh["cache"], sh["tok"], sh["pos"], None)
            fn = jax.jit(draft_step, out_shardings=out_sh, donate_argnums=(1,))
            self._spec_draft_jit = fn
        return fn

    def _spec_verify_fn(self) -> Callable:
        """ONE jitted verify program at fixed chunk width
        ``policy.gamma``: a full-precision ``prefill_chunk`` over the
        round's entry token ``d_0`` plus drafts ``d_1..`` with
        ``return_all_logits``, whose argmax row is each position's true
        next token.  Shallower rounds (per-lane gamma backoff) pad the
        draft operands and mask through ``nval`` — per-lane validity is
        already how ragged chunked prefill works — so the width never
        forks a second program.  The chunk's KV writes overwrite every
        draft-precision row at full precision, so the cache a later
        step reads never depends on the draft planes.  The cache
        operand is donated, same as the draft step."""
        fn = self._spec_verify_jit
        if fn is None:
            engine = self.engine
            cfg = engine.cfg
            V = cfg.vocab_size
            cache_dtype = self.pool.cache_dtype

            if self._tiered:
                # Tiered engines verify at the round's EFFECTIVE plane
                # count (max across participating lanes after any degrade
                # shed) — a runtime operand like the draft's, so tier
                # levels never fork a second verify program.  The floors
                # guarantee it stays strictly above draft_planes.
                def verify(p, cache, tok0, drafts, start, nval, table,
                           planes):
                    with packed_shard_mesh(engine._packed_mesh), \
                         paged_shard_mesh(self._paged_mesh):
                        vin = jnp.concatenate(
                            [tok0] + [d[:, None] for d in drafts], axis=1)
                        with active_plane_count(planes):
                            all_logits, cache = transformer.prefill_chunk(
                                p, cache, vin, start, nval, cfg,
                                cache_dtype=cache_dtype, block_table=table,
                                return_all_logits=True)
                        verified = jnp.argmax(
                            all_logits[..., :V], axis=-1).astype(jnp.int32)
                    return cache, verified
            else:
                def verify(p, cache, tok0, drafts, start, nval, table):
                    with packed_shard_mesh(engine._packed_mesh), \
                         paged_shard_mesh(self._paged_mesh):
                        vin = jnp.concatenate(
                            [tok0] + [d[:, None] for d in drafts], axis=1)
                        all_logits, cache = transformer.prefill_chunk(
                            p, cache, vin, start, nval, cfg,
                            cache_dtype=cache_dtype, block_table=table,
                            return_all_logits=True)
                        verified = jnp.argmax(
                            all_logits[..., :V], axis=-1).astype(jnp.int32)
                    return cache, verified

            out_sh = None
            if engine.mesh is not None:
                out_sh = (self.pool.shardings["cache"], None)
            fn = jax.jit(verify, out_shardings=out_sh, donate_argnums=(1,))
            self._spec_verify_jit = fn
        return fn

    def compiled_decode_programs(self) -> int:
        return int(self._decode._cache_size())

    def decode_program_text(self) -> str:
        """Optimized HLO of the pooled decode program, compiled for the
        pool's current operands: what the device really runs (a Pallas
        kernel shows as ``tpu_custom_call``)."""
        pool = self.pool
        args = (self.engine.params, pool.cache, pool.tok, pool.pos, pool.act,
                pool.block_table)
        if self._tiered:
            args += (jnp.int32(self._n_bits),)
        return self._decode.lower(*args).compile().as_text()

    def compiled_prefill_programs(self) -> int:
        """Prefill-side compiled programs: legacy admission compiles one
        per distinct prompt length (grows with the workload); chunked
        prefill compiles one per chunk size actually used (bounded by
        ``policy.chunk_sizes``, independent of the length mix)."""
        if self.policy.chunked_prefill:
            return sum(int(fn._cache_size()) for fn in self._chunk_cache.values())
        return sum(int(fn._cache_size()) for fn in self._prefill_cache.values())

    def compiled_admit_programs(self) -> int:
        """Chunked multi-admit programs (fixed-size padded slot vector =>
        stays 1 regardless of burst sizes)."""
        return int(self._reset_slots._cache_size())

    def compiled_spec_programs(self) -> int:
        """Spec-round compiled programs: ONE draft step (round depth is
        the dispatch count, draft precision a runtime operand) plus ONE
        fixed-width verify chunk — 2 total, independent of ``gamma``
        and ``draft_planes``."""
        return sum(int(fn._cache_size())
                   for fn in (self._spec_draft_jit, self._spec_verify_jit)
                   if fn is not None)

    # -- precision tiers + degrade loop --------------------------------------
    def _floor(self, precision: str) -> int:
        """The plane count class ``precision`` may not be degraded below.
        User floors default to 1; with spec_decode the floor is raised to
        draft_planes + 1 so a degraded lane's verify always runs strictly
        above the draft precision (the satellite clamp)."""
        fl = max(1, self._floors.get(precision, 1))
        if self.policy.spec_decode:
            fl = max(fl, self.policy.draft_planes + 1)
        return fl

    def _effective(self, precision: str) -> int:
        """Effective plane count for precision class ``precision`` under
        the current shed level: ``max(floor, tier_planes - shed)``."""
        k = self._tier_planes.get(precision, self._n_bits)
        return max(min(self._floor(precision), k), k - self._shed)

    def _effective_planes(self, s: SlotState) -> int:
        """Effective plane count lane ``s`` decodes at this step."""
        k = s.planes if s.planes is not None else self._n_bits
        return max(min(self._floor(s.precision), k), k - self._shed)

    def _set_plane_gauges(self) -> None:
        for name in self._tier_planes:
            self._g_active_planes.labels(tier=name).set(self._effective(name))

    def _resolve_planes(self, precision, uid=None) -> Tuple[int, str]:
        """Validate Request.precision and resolve it to (planes, class).

        "full" -> n_bits; a tier-table key -> its entry; an int -> that
        explicit plane count (class "explicit" for floor lookups).
        Raises ValueError with the same up-front discipline as the tier
        check in :meth:`stream`."""
        who = f"request {uid}: " if uid is not None else ""
        if precision in ("full", None):
            return self._n_bits, "full"
        if isinstance(precision, str):
            k = self._tier_planes.get(precision)
            if k is None:
                raise ValueError(
                    f"{who}unknown precision class {precision!r} — want "
                    f"'full', one of {sorted(self._tier_planes)}, or an "
                    "explicit plane count"
                )
            return k, precision
        k = int(precision)
        if not 1 <= k <= self._n_bits:
            raise ValueError(
                f"{who}precision={precision!r} — an explicit plane count "
                f"must be in [1, n_bits={self._n_bits}]"
            )
        if self.policy.spec_decode and k <= self.policy.draft_planes:
            raise ValueError(
                f"{who}precision={k} planes <= draft_planes="
                f"{self.policy.draft_planes} — the effective serving "
                "precision must be strictly above the draft precision"
            )
        return k, "explicit"

    def _record_transition(self, direction: str, now: int) -> None:
        """One shed/restore transition: counter + per-tier gauges + a
        trace span on every live lane carrying its NEW effective count."""
        self._c_degrade.labels(direction=direction).inc()
        if direction == "shed":
            self.degrade_sheds += 1
        else:
            self.degrade_restores += 1
        self._set_plane_gauges()
        kind = (obs_trace.PLANES_SHED if direction == "shed"
                else obs_trace.PLANES_RESTORED)
        rec = self.obs.recorder
        for s in self.pool.slots:
            if s.uid is not None:
                rec.event(s.uid, kind, shed=self._shed,
                          planes=self._effective_planes(s))

    def _degrade_tick(self, queue_len: int, now: int) -> None:
        """One step of the load-triggered degrade loop (policy.degrade).

        Pressure = queue backed up past ``degrade_queue_depth``, OR every
        lane busy (``degrade_occupancy``) with work still queued, OR the
        windowed preemption rate past ``degrade_preempt_rate``.  Each
        pressured step sheds one plane (every tier, floor-clamped);
        ``degrade_hysteresis`` consecutive calm steps restore one — the
        asymmetry keeps the loop from flapping at the threshold.  The
        ``force_shed`` hook replaces the triggers with an exact schedule
        (still clamped) for deterministic conformance testing."""
        pol = self.policy
        self._preempt_window.append(self._preempt_step)
        self._preempt_step = 0
        if self.force_shed is not None:
            target = min(max(int(self.force_shed(now)), 0), self._shed_ceiling)
            while self._shed < target:
                self._shed += 1
                self._record_transition("shed", now)
            while self._shed > target:
                self._shed -= 1
                self._record_transition("restore", now)
            return
        occ = self.pool.n_active / max(self.pool.n_slots, 1)
        prate = sum(self._preempt_window) / max(len(self._preempt_window), 1)
        pressure = (
            queue_len >= pol.degrade_queue_depth
            or (queue_len > 0 and occ >= pol.degrade_occupancy)
            or prate > pol.degrade_preempt_rate
        )
        if pressure:
            self._calm = 0
            if self._shed < self._shed_ceiling:
                self._shed += 1
                self._record_transition("shed", now)
            elif pol.spec_decode and not self._degrade_warned:
                import warnings

                warnings.warn(
                    f"degrade loop clamped at shed={self._shed}: every tier "
                    f"sits at its floor (>= draft_planes + 1 = "
                    f"{pol.draft_planes + 1} under spec_decode) — shedding "
                    "further would make the verify as imprecise as the draft",
                    RuntimeWarning, stacklevel=2)
                self._degrade_warned = True
        else:
            self._calm += 1
            if self._shed > 0 and self._calm >= pol.degrade_hysteresis:
                self._shed -= 1
                self._calm = 0
                self._record_transition("restore", now)

    # -- admission ---------------------------------------------------------
    def _first_chunk_blocks(self, plen: int) -> int:
        """Blocks the lane's FIRST prefill chunk will demand."""
        rows = min(plen, max(self.policy.chunk_sizes))
        return self.pool.allocator.blocks_for_rows(rows)

    def _lifetime_blocks(self, req) -> int:
        """Worst-case blocks over the request's life: prompt rows plus
        max_new - 1 decode writes (same row math as the max_len check)."""
        return self.pool.allocator.blocks_for_rows(len(req.tokens) + req.max_new - 1)

    def _paged_assign(
        self, order: List[_Pending], free: List[int]
    ) -> List[Tuple[_Pending, int]]:
        """Paged lane assignment: a free lane is no longer enough — each
        admit must find a lane whose *shard* has (a) free blocks >= its
        first-chunk demand (immediate progress: a fresh admit always
        lands its first chunk before it can be chosen as a victim, so
        overcommit cannot livelock on admit -> self-preempt) and (b)
        uncommitted capacity >= its worst-case lifetime demand, measured
        against ``commit_capacity = shard_blocks * overcommit`` (at the
        default factor 1.0 this is the physical pool and on-demand
        growth can never fail — see slots.BlockAllocator; past 1.0 the
        scheduler preempts to headroom instead).

        With a replicated table (one shard) every lane sees the same
        budgets and the assignment degenerates to free-list order.  With
        sharded tables (lanes and pool blocks co-sharded over the data
        axes) each lane draws only on its own shard's range, so the walk
        picks the first free lane whose shard fits.  ``order`` is the
        tier-priority queue view (FIFO within a tier) and the walk STOPS
        at the first request that fits no lane; it retries when an
        eviction frees blocks, and nothing jumps it."""
        alloc = self.pool.allocator
        budget_free = [alloc.free_in(s) for s in range(alloc.n_shards)]
        budget_commit = [alloc.commit_capacity - alloc.committed_in(s)
                         for s in range(alloc.n_shards)]
        lanes = list(free)
        pairs: List[Tuple[_Pending, int]] = []
        for pend in order:
            if not lanes:
                break
            first = self._first_chunk_blocks(pend.prompt_len)
            life = self._lifetime_blocks(pend.request)
            chosen = None
            for lane in lanes:
                sh = self.pool.lane_shard(lane)
                if first <= budget_free[sh] and life <= budget_commit[sh]:
                    chosen = lane
                    break
            if chosen is None:
                break  # head-of-line: nothing jumps the unfit request
            lanes.remove(chosen)
            sh = self.pool.lane_shard(chosen)
            budget_free[sh] -= first
            budget_commit[sh] -= life
            pairs.append((pend, chosen))
        return pairs

    def _priority_order(self, queue: Deque[_Pending], now: int) -> List[_Pending]:
        """Admission order: latency-tier (and aged-past-``aging_steps``)
        requests first, FIFO by global sequence within a class.  The sort
        is stable and keyed on ``seq``, so an all-default-tier workload
        reduces exactly to the old FIFO."""
        aging = self.policy.aging_steps

        def key(pend: _Pending):
            waited = now - (pend.enqueued_at if pend.enqueued_at is not None
                            else now)
            urgent = pend.tier == "latency" or waited >= aging
            return (0 if urgent else 1, pend.seq)

        return sorted(queue, key=key)

    def _admit(self, queue: Deque[_Pending], now: int):
        # Take the free list ONCE: re-deriving free_slots()[0] per placement
        # was O(n_slots^2) per burst and would mis-place if a multi-admit
        # reordered frees mid-loop.
        free = self.pool.free_slots()
        if not queue:
            return
        if not free:
            self._c_blocked.inc()  # queued work, no lane
            return
        order = self._priority_order(queue, now)
        if self.policy.paged:
            pairs = self._paged_assign(order, free)
        else:
            pairs = list(zip(order, free))
        placeable = len(pairs)
        if placeable == 0:
            self._c_blocked.inc()  # lanes free, but no shard fits the head
            return
        oldest_wait = now - (order[0].enqueued_at if order[0].enqueued_at is not None else now)
        if placeable < self.policy.min_admit and oldest_wait < self.policy.max_wait:
            return  # max-wait batching: hold for a fuller admission burst
        batch = [pend for pend, _ in pairs]
        for pend in batch:
            queue.remove(pend)
        slots = [lane for _, lane in pairs]
        self.admit_bursts.append(placeable)
        self._h_burst.observe(placeable)
        if self.policy.chunked_prefill:
            self._admit_chunked(batch, slots, now)
        else:
            self._admit_legacy(batch, slots, now)

    def _admit_legacy(self, batch: List[_Pending], slots: List[int], now: int):
        # Every request's ADMITTED span starts at the burst wall clock, so
        # TTFT includes the serialisation behind earlier batch-1 prefills
        # in the same burst (the cost multi-admit removes).
        wall = obs_trace.now()
        rec = self.obs.recorder
        for pend, slot in zip(batch, slots):
            req = pend.request
            tr = rec.get(req.uid)
            tr.event(obs_trace.ADMITTED, ts=wall, slot=slot)
            plen = len(req.tokens)
            toks = self.engine._place_batch(
                jnp.asarray(np.asarray(req.tokens, np.int32)[None, :])
            )
            logits, self.pool.cache = self._prefill_fn(plen)(
                self.engine.params, self.pool.cache, toks, jnp.int32(slot)
            )
            first = self.engine._sample(
                logits,
                jnp.asarray([req.temperature], jnp.float32),
                req.temperature > 0,
            )
            first_host = int(np.asarray(first)[0])
            tr.event(obs_trace.FIRST_TOKEN)
            ttft_ms = tr.ttft_ms()
            self._h_ttft.observe(ttft_ms)
            self._h_tier_ttft.labels(tier=pend.tier).observe(ttft_ms)
            self.pool.occupy(
                slot, req.uid, first_host, plen, req.max_new,
                req.temperature, ttft_ms, now,
            )

    def _admit_chunked(self, batch: List[_Pending], slots: List[int], now: int):
        """Fused multi-admit: every placeable request claims its lane in one
        device dispatch; the prompts then stream through chunk steps."""
        wall = obs_trace.now()
        rec = self.obs.recorder
        slots_vec = np.full((self.pool.n_slots,), self.pool.n_slots, np.int32)
        slots_vec[: len(slots)] = slots
        self.pool.cache = self._reset_slots(
            self.pool.cache, self._place_ctrl("slots", slots_vec)
        )
        for pend, slot in zip(batch, slots):
            req = pend.request
            self._admit_seq += 1
            planes, prec = (self._resolve_planes(pend.precision, uid=req.uid)
                            if self._tiered else (None, "full"))
            self.pool.admit(
                slot, req.uid, pend.prompt_tokens(), pend.max_new,
                req.temperature, now, wall, tier=pend.tier, prior=pend.prior,
                admit_seq=self._admit_seq, planes=planes, precision=prec,
                prior_planes=pend.prior_planes,
            )
            if self.policy.spec_decode:
                # Fresh lanes (and preempted resumes) start at the full
                # policy draft depth; per-round backoff takes over.
                self.pool.slots[slot].spec_gamma = self.policy.gamma
            self._lane_pend[slot] = pend
            attrs = {"slot": slot}
            if self.policy.paged:
                attrs["blocks"] = self.pool.slots[slot].committed
            if self._tiered:
                attrs["planes"] = planes
            tr = rec.get(req.uid)
            tr.event(obs_trace.ADMITTED, ts=wall, **attrs)
            if pend.prior is not None:
                # Resumed after a preemption: the recompute prefill over
                # prompt + generated-so-far starts here (prior is [] when
                # the victim was still mid-prefill — nothing generated,
                # but the re-run is still recompute work worth marking).
                tr.event(obs_trace.RE_PREFILL, ts=wall,
                         rows=pend.prompt_len, generated=len(pend.prior))

    # -- chunked prefill ---------------------------------------------------
    def _pick_chunk(self, max_remaining: int, n_decoding: int = 0) -> int:
        """Occupancy-aware chunk size, always drawn from
        ``policy.chunk_sizes`` (the compiled prefill set stays bounded by
        the table).  Two forces:

        * cover: the smallest configured chunk covering the longest
          remaining prompt, else the largest (multi-chunk prompts) — the
          static rule this replaces, and the whole rule when no lane is
          decoding or ``occupancy_chunking`` is off.
        * occupancy: with ``f = n_decoding / n_slots`` live decode lanes,
          step down the sorted size table by ``f`` — each prefill chunk
          rides the same dispatch cadence as the interleaved decode
          steps, so a hot pool prefers small chunks (low added per-token
          latency for live lanes) and a draining pool large ones (fast
          prompt consumption).  Monotone non-increasing in occupancy.
        """
        sizes = sorted(self.policy.chunk_sizes)
        cover = next((c for c in sizes if c >= max_remaining), sizes[-1])
        if not self.policy.occupancy_chunking or n_decoding <= 0:
            return cover
        frac = n_decoding / max(self.pool.n_slots, 1)
        desc = sizes[::-1]
        idx = min(int(frac * len(desc)), len(desc) - 1)
        return min(cover, desc[idx])

    def _place_ctrl(self, name: str, arr: np.ndarray) -> jax.Array:
        if self._chunk_shardings is None:
            return jnp.asarray(arr)
        return jax.device_put(jnp.asarray(arr), self._chunk_shardings[name])

    def _preempt(self, slot: int, queue: Deque[_Pending], now: int) -> None:
        """Recompute-swap preemption of lane ``slot``: snapshot its
        generated tokens, free its blocks + commitment, and re-enqueue
        the request with prompt + generated-so-far as its resume prompt.
        The trace stays OPEN (``preempted`` is not terminal) and records
        ``admitted``/``re_prefill`` again on re-admission, so TTFT — the
        span to the FIRST ``first_token`` — is unaffected."""
        pool = self.pool
        s = pool.slots[slot]
        pend = self._lane_pend.pop(slot)
        gen = list(s.prior or []) + list(s.tokens or [])
        gen_planes = (list(s.prior_planes or []) + list(s.plane_log or [])
                      if self._tiered else None)
        rows_lost = (s.filled if s.phase == "prefill"
                     else len(s.prompt) + len(s.tokens) - 1)
        self.obs.recorder.event(
            s.uid, obs_trace.PREEMPTED, slot=slot, phase=s.phase,
            generated=len(gen), blocks=len(s.blocks or ()),
        )
        self._c_preempt.labels(tier=s.tier).inc()
        self._c_preempt_rows.inc(rows_lost)
        self._preempt_step += 1
        pool.evict(slot)
        queue.append(_Pending(pend.request, pend.arrival, enqueued_at=now,
                              seq=pend.seq, prior=gen,
                              prior_planes=gen_planes))

    def _ensure_headroom(self, demand: Dict[int, int],
                         queue: Deque[_Pending], now: int) -> Dict[int, int]:
        """Make this step's block demand (lane -> target cache rows)
        grantable in every shard, preempting victims where it is not —
        the step that turns overcommit's IOU into progress.  Returns the
        demand with preempted lanes dropped (a demanding lane may itself
        be the victim).

        Termination and deadlock-freedom: victims are drawn per shard in
        :func:`preemption_order` from live lanes that either hold blocks
        or are demanding (preempting anything else frees nothing), each
        preemption strictly shrinks that candidate set, and a lane ALONE
        in its shard always fits — its lifetime need is bounded by the
        shard's physical blocks by the up-front rejection in
        :meth:`stream` — so the loop cannot run dry while demand is
        unmet, and the highest-priority lane is preempted last, i.e.
        always runs to completion.  At ``overcommit == 1.0`` the
        reservation invariant makes every demand fit up front and this
        is a no-op."""
        pool, alloc = self.pool, self.pool.allocator
        demand = dict(demand)

        def shard_need(sh: int) -> int:
            return sum(
                max(0, alloc.blocks_for_rows(rows) - len(pool.slots[i].blocks))
                for i, rows in demand.items() if pool.lane_shard(i) == sh
            )

        for sh in range(alloc.n_shards):
            while shard_need(sh) > alloc.free_in(sh):
                cands = [
                    (i, pool.slots[i])
                    for i in dist_sharding.shard_lanes(
                        sh, pool.n_slots, pool.table_shards)
                    if pool.slots[i].uid is not None
                    and (pool.slots[i].blocks or i in demand)
                ]
                if len(cands) < 2:
                    raise RuntimeError(
                        f"shard {sh}: demand {shard_need(sh)} blocks > free "
                        f"{alloc.free_in(sh)} with {len(cands)} candidate "
                        "lane(s) — the up-front per-request capacity check "
                        "should make a sole lane always fit"
                    )
                victim = preemption_order(cands)[0][0]
                self._preempt(victim, queue, now)
                demand.pop(victim, None)
        return demand

    def _prefill_step(self, queue: Deque[_Pending], now: int):
        """One prefill_chunk dispatch: every prefilling lane consumes up to
        C prompt tokens; lanes whose prompt completes sample their first
        token and flip to the decode phase."""
        pool = self.pool
        # Under overcommit the headroom pass may preempt lanes — including
        # prefilling ones, which changes the lane set and the chunk-size
        # choice — so recompute until the demand fits as-is.
        while True:
            lanes = pool.prefilling()
            if not lanes:
                return  # every prefilling lane was preempted this step
            remaining = {
                i: len(pool.slots[i].prompt) - pool.slots[i].filled
                for i in lanes
            }
            C = self._pick_chunk(max(remaining.values()), pool.n_decoding)
            if not self.policy.paged:
                break
            demand = {
                i: pool.slots[i].filled + min(C, remaining[i]) for i in lanes
            }
            if self._ensure_headroom(demand, queue, now) == demand:
                # alloc-on-demand: grant the blocks each lane's chunk rows
                # [filled, filled + take) land in before dispatch (one
                # batched table update for the whole chunk)
                pool.grow_many(demand)
                break
        toks = np.zeros((pool.n_slots, C), np.int32)
        # Non-prefilling lanes point past the cache: every write drops and
        # n_valid=0 makes their recurrence a no-op (see prefill_chunk).
        start = np.full((pool.n_slots,), self.engine.max_len, np.int32)
        nval = np.zeros((pool.n_slots,), np.int32)
        for i in lanes:
            s = pool.slots[i]
            take = min(C, remaining[i])
            toks[i, :take] = s.prompt[s.filled : s.filled + take]
            start[i] = s.filled
            nval[i] = take
        last_logits, pool.cache = self._chunk_fn(C)(
            self.engine.params, pool.cache,
            self._place_ctrl("tok", toks),
            self._place_ctrl("start", start),
            self._place_ctrl("nvalid", nval),
            pool.block_table,
        )
        done = [i for i in lanes if pool.slots[i].filled + int(nval[i])
                == len(pool.slots[i].prompt)]
        sampled_host = None
        if done:
            sampled = self.engine._sample(last_logits, pool.temps, pool.any_hot)
            sampled_host = np.asarray(sampled)
        self.prefill_chunks += 1
        self._c_chunks.inc()
        rec = self.obs.recorder
        for i in lanes:
            s = pool.slots[i]
            tr = rec.get(s.uid)
            tr.event(obs_trace.PREFILL_CHUNK, size=int(nval[i]))
            s.filled += int(nval[i])
            if s.filled == len(s.prompt):
                if tr.find(obs_trace.FIRST_TOKEN) is None:
                    # A lane resumed after a decode-phase preemption
                    # already emitted its first token in its first life —
                    # recording (and observing) TTFT again would double
                    # count the request.
                    tr.event(obs_trace.FIRST_TOKEN)
                    ttft_ms = tr.ttft_ms()
                    self._h_ttft.observe(ttft_ms)
                    self._h_tier_ttft.labels(tier=s.tier).observe(ttft_ms)
                else:
                    ttft_ms = tr.ttft_ms()
                pool.start_decode(i, int(sampled_host[i]), ttft_ms)
                if self._tiered:
                    # The first token comes off the full-precision
                    # prefill chunk, whatever the lane's tier.
                    s.plane_log = [self._n_bits]

    # -- speculative decoding ----------------------------------------------
    def _spec_round(self, queue: Deque[_Pending], now: int) -> None:
        """One draft+verify round over every decode-phase lane.

        Lane ``i`` at ``pos0 = plen + g - 1`` (last token ``d_0`` sampled
        but its KV row unwritten — the pool's steady-state convention)
        drafts ``gamma_i = min(spec_gamma, remaining)`` tokens at draft
        precision, then the verify chunk scores rows ``pos0 ..
        pos0+gamma_i-1`` (inputs ``d_0..d_{gamma_i-1}``) at full
        precision, overwriting every draft-precision KV row.  With
        ``a`` = longest prefix where draft ``d_{j+1}`` equals verified
        ``v_j``, the lane commits ``d_1..d_a`` plus the correction
        ``v_a`` when a draft was rejected (``a < gamma_i``) — always
        >= 1 token, so every round makes progress — and rewinds past
        the rejected rows by decrementing its position and returning
        tail blocks (``SlotPool.commit_spec``).  Committed tokens are
        verify outputs given an exactly-reproduced prefix, so greedy
        output is token-identical to non-speculative decode.

        Round setup is the ONLY point this path can preempt: the verify
        writes no row the draft demand did not cover, and draft tokens
        live in round-local state until commit — a preemption snapshot
        (``prior + s.tokens``) can never contain an unverified draft."""
        pool = self.pool
        # Under overcommit the headroom pass may preempt lanes —
        # including round participants — so recompute until the demand
        # fits as-is (same discipline as _prefill_step).
        while True:
            lanes = [i for i, s in enumerate(pool.slots)
                     if s.uid is not None and s.phase == "decode"]
            if not lanes:
                return  # every decode lane was preempted this step
            gam: Dict[int, int] = {}
            demand: Dict[int, int] = {}
            for i in lanes:
                s = pool.slots[i]
                gam[i] = max(1, min(s.spec_gamma, s.remaining))
                # Last verify write row is plen+g+gamma_i-2, so rows
                # [0, plen+g+gamma_i-1) must be granted; gamma_i <=
                # remaining keeps this within the lifetime reservation
                # (the headroom/deadlock-freedom argument is unchanged).
                demand[i] = len(s.prompt) + len(s.tokens) + gam[i] - 1
            if self._ensure_headroom(demand, queue, now) == demand:
                pool.grow_many(demand)
                break
        gamma_r = max(gam.values())
        B = pool.n_slots
        act_rows = np.zeros((gamma_r, B), np.bool_)
        start = np.full((B,), self.engine.max_len, np.int32)
        nval = np.zeros((B,), np.int32)
        for i in lanes:
            s = pool.slots[i]
            act_rows[: gam[i], i] = True
            start[i] = len(s.prompt) + len(s.tokens) - 1  # pos0
            nval[i] = gam[i]
        self._h_attn.observe(sum(len(pool.slots[i].blocks) for i in lanes))
        t0 = time.perf_counter()
        draft_fn = self._spec_draft_fn()
        verify_fn = self._spec_verify_fn()
        params = self.engine.params
        planes = jnp.int32(self.policy.draft_planes)
        table = pool.block_table
        tok0 = pool.tok  # round entry token d_0 per lane (verify col 0)
        cache, tok, pos = pool.cache, tok0, pool.pos
        # gamma_r async draft dispatches chained on device (tok/pos
        # carry), then one verify dispatch, then ONE host sync.  The
        # cache flows through donated operands the whole way, so
        # pool.cache is dead from the first dispatch until the
        # reassignment below — nothing in between may touch it.
        drafts = []
        for j in range(gamma_r):
            cache, tok, pos, nxt = draft_fn(
                params, cache, tok, pos,
                pool._pin("act", jnp.asarray(act_rows[j])), table, planes)
            drafts.append(nxt)
        # Pad the verify's draft operands to the fixed program width
        # with a handle that is already live; nval masks them out.
        width = self.policy.gamma - 1
        vdrafts = tuple(drafts[: gamma_r - 1]) + \
            (drafts[-1],) * (width - (gamma_r - 1))
        vargs = (params, cache, tok0, vdrafts,
                 self._place_ctrl("start", start),
                 self._place_ctrl("nvalid", nval),
                 table)
        vplanes = None
        if self._tiered:
            # Verify at the round's effective plane count: max across
            # the participating lanes' tiers after the degrade shed.
            # Committed tokens are verify outputs, so this is the count
            # their plane_log records.
            vplanes = max(self._effective_planes(pool.slots[i])
                          for i in lanes)
            vargs = vargs + (jnp.int32(vplanes),)
        pool.cache, verified = verify_fn(*vargs)
        # drafts_h[j][i] = d_{j+1} for lane i; ver_h[i, j] = v_j (columns
        # past gam[i] are padding and never read).
        drafts_h, ver_h = jax.device_get((drafts, verified))
        step_ms = (time.perf_counter() - t0) * 1e3
        rec = self.obs.recorder
        tok_fix, tok_vals, pos_vals = [], [], []
        acc_total = rej_total = commit_total = 0
        for i in lanes:
            s = pool.slots[i]
            g_i = gam[i]
            a = 0
            while a < g_i and int(drafts_h[a][i]) == int(ver_h[i, a]):
                a += 1
            if a < g_i:
                committed = [int(drafts_h[j][i]) for j in range(a)]
                committed.append(int(ver_h[i, a]))  # the correction v_a
            else:
                committed = [int(drafts_h[j][i]) for j in range(g_i)]
            freed = pool.commit_spec(i, committed)
            # Per-lane depth backoff: a fully-accepted round earns a
            # deeper next draft (up to the policy gamma); a fully
            # rejected one halves it (floor 1).
            if a == g_i:
                s.spec_gamma = min(s.spec_gamma + 1, self.policy.gamma)
            elif a == 0:
                s.spec_gamma = max(1, s.spec_gamma // 2)
            if a < g_i:
                # Rejection: the draft chain's tok/pos overshot this
                # lane — rewind to the correction and committed length.
                tok_fix.append(i)
                tok_vals.append(committed[-1])
                pos_vals.append(len(s.prompt) + len(s.tokens) - 1)
            if self._tiered:
                s.plane_log.extend([vplanes] * len(committed))
            rec.event(s.uid, obs_trace.DRAFT, steps=g_i)
            if self._tiered:
                rec.event(s.uid, obs_trace.VERIFY, accepted=a,
                          committed=len(committed), planes=vplanes)
            else:
                rec.event(s.uid, obs_trace.VERIFY, accepted=a,
                          committed=len(committed))
            if a < g_i:
                rec.event(s.uid, obs_trace.ROLLBACK, rejected=g_i - a,
                          freed_blocks=freed)
            acc_total += a
            rej_total += g_i - a
            commit_total += len(committed)
        # The draft chain's final tok/pos are already correct for
        # fully-accepted lanes (last draft = last committed, pos
        # advanced gamma_i) and untouched for inactive lanes, so a
        # full-accept round — the steady state once acceptance is high
        # — needs ZERO scatter dispatches here.
        if tok_fix:
            fix_idx = jnp.asarray(tok_fix)
            tok = tok.at[fix_idx, 0].set(jnp.asarray(tok_vals, jnp.int32))
            pos = pos.at[fix_idx].set(jnp.asarray(pos_vals, jnp.int32))
        pool.tok = pool._pin("tok", tok)
        pool.pos = pool._pin("pos", pos)
        # One round = one pooled dispatch on the decode clock.
        self.decode_ms_total += step_ms
        self._h_step.observe(step_ms)
        self.decode_steps += 1
        self._c_steps.inc()
        self.spec_rounds += 1
        self.spec_drafted += acc_total + rej_total
        self.spec_accepted += acc_total
        self.spec_committed += commit_total
        self._c_spec_rounds.inc()
        self._c_spec_draft.inc(acc_total + rej_total)
        self._c_spec_accept.inc(acc_total)
        self._c_spec_reject.inc(rej_total)
        if self.spec_drafted:
            self._g_spec_rate.set(self.spec_accepted / self.spec_drafted)
        self._h_occ.observe(len(lanes))
        used = pool.allocator.used_count
        live = pool.live_rows()
        self._h_blocks.observe(used)
        self._h_rows.observe(live)
        if used:
            self._h_frag.observe(1.0 - live / (used * pool.block_size))

    # -- main loop ---------------------------------------------------------
    def stream(
        self,
        requests: Sequence["repro.serve.engine.Request"],  # noqa: F821
        arrival_steps: Optional[Sequence[int]] = None,
    ) -> Iterator["repro.serve.engine.Result"]:  # noqa: F821
        """Run the workload; yield each Result the step its lane finishes.

        ``arrival_steps[i]`` is the scheduler step at which requests[i]
        becomes visible (default: all at step 0).  FIFO by arrival then
        submission order.
        """
        if arrival_steps is None:
            arrival_steps = [0] * len(requests)
        if len(arrival_steps) != len(requests):
            raise ValueError(
                f"arrival_steps has {len(arrival_steps)} entries for "
                f"{len(requests)} requests — zip would silently drop the excess"
            )
        for r in requests:
            tier = getattr(r, "tier", "throughput")
            if tier not in ("latency", "throughput"):
                raise ValueError(
                    f"request {r.uid}: unknown SLO tier {tier!r} — want "
                    "'latency' or 'throughput'"
                )
            prec = getattr(r, "precision", "full")
            if self._tiered:
                self._resolve_planes(prec, uid=r.uid)  # raises on bad
            elif prec not in ("full", None):
                raise ValueError(
                    f"request {r.uid}: precision={prec!r} but this engine "
                    "has no precision tiers — configure "
                    "SchedulerPolicy(precision_tiers=...) (or "
                    "ServeEngine(precision_tiers=...)) to serve reduced "
                    "plane counts"
                )
            if len(r.tokens) < 1:
                raise ValueError(
                    f"request {r.uid}: empty prompt — there is no position to "
                    "prefill and the lane would never leave the prefill phase"
                )
            if self.policy.spec_decode and r.temperature > 0:
                raise ValueError(
                    f"request {r.uid}: temperature={r.temperature} — "
                    "spec_decode accepts drafts by greedy verify; a sampled "
                    "lane would silently diverge from its non-speculative "
                    "output"
                )
            if r.max_new < 1:
                raise ValueError(
                    f"request {r.uid}: max_new={r.max_new} — the slot pool "
                    "always emits the prefill-sampled token, so max_new < 1 "
                    "would silently diverge from the bucketed engine's "
                    "zero-token output (and break the capacity check below)"
                )
            # last cache row written: prompt rows 0..plen-1, then max_new-1
            # decode writes at plen..plen+max_new-2
            need = len(r.tokens) + r.max_new - 1
            if need > self.engine.max_len:
                raise ValueError(
                    f"request {r.uid}: prompt {len(r.tokens)} + {r.max_new - 1} "
                    f"decode writes need {need} cache rows > max_len "
                    f"{self.engine.max_len} — out-of-range cache writes would "
                    "be silently dropped and the output would be garbage"
                )
            if self.policy.paged:
                # Up-front rejection measures against the shard's PHYSICAL
                # blocks, NOT the overcommitted commitment capacity — a
                # request bigger than the pool could be committed but
                # never grown, and this rule is also what guarantees a
                # lane alone in its shard always fits (the base case of
                # _ensure_headroom's deadlock-freedom argument).
                cap = self.pool.allocator.shard_blocks  # == n_blocks unsharded
                if self._lifetime_blocks(r) > cap:
                    raise ValueError(
                        f"request {r.uid}: needs {self._lifetime_blocks(r)} KV "
                        f"blocks worst-case > per-lane pool capacity {cap} "
                        f"({self.pool.n_blocks} blocks / "
                        f"{self.pool.table_shards} table shard(s)) — it could "
                        "never be admitted (raise n_blocks or shrink "
                        "prompt/max_new)"
                    )
        incoming = sorted(
            (_Pending(r, int(t)) for r, t in zip(requests, arrival_steps)),
            key=lambda p: p.arrival,
        )
        for seq, pend in enumerate(incoming):
            pend.seq = seq  # FIFO sequence, stable across preemption requeues
        incoming = deque(incoming)
        queue: Deque[_Pending] = deque()
        pool = self.pool
        rec = self.obs.recorder
        now = 0
        try:
            while incoming or queue or pool.n_active:
                while incoming and incoming[0].arrival <= now:
                    pend = incoming.popleft()
                    pend.enqueued_at = now
                    rec.begin(pend.request.uid, arrival=pend.arrival)
                    queue.append(pend)
                self._g_queue.set(len(queue))
                self._admit(queue, now)
                if self._tiered and self.policy.degrade:
                    # Load-triggered plane shedding: measured AFTER the
                    # admission pass, so "queue backed up" means work
                    # that genuinely could not be placed this step.
                    self._degrade_tick(len(queue), now)
                # Evict lanes whose request finished at admission
                # (legacy max_new == 1).
                for ev in self._finished():
                    yield ev
                worked = False
                if self.policy.chunked_prefill and pool.prefilling():
                    self._prefill_step(queue, now)
                    worked = True
                    # chunked max_new == 1: finished at first token
                    for ev in self._finished():
                        yield ev
                if self.policy.spec_decode and pool.n_decoding:
                    # Speculative rounds replace the single pooled decode
                    # step: gamma draft steps + one verify per dispatch,
                    # committing 1..gamma tokens per lane (block growth,
                    # headroom preemption and rewind live inside).
                    worked = True
                    self._spec_round(queue, now)
                    for ev in self._finished():
                        yield ev
                elif pool.n_decoding:
                    worked = True
                    if self.policy.paged:
                        # decode growth: lanes crossing a block boundary
                        # need their next block granted before the write
                        # (one batched table update for the whole step).
                        # Under overcommit the headroom pass may first
                        # preempt victims — possibly every decode lane —
                        # so the dispatch below re-checks n_decoding.
                        pool.grow_many(self._ensure_headroom({
                            i: len(s.prompt) + len(s.tokens)
                            for i, s in enumerate(pool.slots)
                            if s.uid is not None and s.phase == "decode"
                        }, queue, now))
                        # blocks this step's attention actually reads: the
                        # decode lanes' live blocks (== the paged kernel's
                        # per-step HBM traffic; the gather path reads
                        # blocks_per_lane per live lane regardless)
                        self._h_attn.observe(sum(
                            len(s.blocks) for s in pool.slots
                            if s.uid is not None and s.phase == "decode"
                        ))
                if not self.policy.spec_decode and pool.n_decoding:
                    t0 = time.perf_counter()
                    active = pool.decode_mask  # lanes live during this decode step
                    if self._tiered:
                        # Group the step's decode lanes by effective plane
                        # count and run one pooled dispatch per distinct
                        # count (grouping off: one dispatch at the max
                        # count serves every lane).  Still ONE compiled
                        # program — the count is a traced operand and the
                        # group's act mask is data.  Each group's sampled
                        # tokens merge into tok/pos under its own mask, so
                        # a later group's dispatch cannot clobber an
                        # earlier group's pending token.
                        eff = {i: self._effective_planes(pool.slots[i])
                               for i in range(pool.n_slots) if active[i]}
                        if self.policy.plane_grouping:
                            groups: Dict[int, List[int]] = {}
                            for i, k in eff.items():
                                groups.setdefault(k, []).append(i)
                        else:
                            groups = {max(eff.values()): sorted(eff)}
                        sampled_host = np.zeros((pool.n_slots,), np.int32)
                        lane_planes: Dict[int, int] = {}
                        # Descending plane order: deterministic, and the
                        # costliest group goes first.
                        for k in sorted(groups, reverse=True):
                            gmask = np.zeros((pool.n_slots,), np.bool_)
                            gmask[groups[k]] = True
                            act_g = pool._pin("act", jnp.asarray(gmask))
                            logits, pool.cache = self._decode(
                                self.engine.params, pool.cache, pool.tok,
                                pool.pos, act_g, pool.block_table,
                                jnp.int32(k),
                            )
                            sampled = self.engine._sample(
                                logits, pool.temps, pool.any_hot)
                            pool.tok = pool._pin("tok", jnp.where(
                                jnp.asarray(gmask)[:, None],
                                sampled[:, None], pool.tok))
                            g_host = np.asarray(sampled)
                            sampled_host[gmask] = g_host[gmask]
                            for i in groups[k]:
                                lane_planes[i] = k
                    else:
                        logits, pool.cache = self._decode(
                            self.engine.params, pool.cache, pool.tok, pool.pos, pool.act,
                            pool.block_table,
                        )
                        sampled = self.engine._sample(logits, pool.temps, pool.any_hot)
                        sampled_host = np.asarray(sampled)  # one host sync per step (streaming)
                        pool.tok = pool._pin("tok", sampled[:, None])
                    step_ms = (time.perf_counter() - t0) * 1e3
                    self.decode_ms_total += step_ms
                    self._h_step.observe(step_ms)
                    self.decode_steps += 1
                    self._c_steps.inc()
                    pool.advance(sampled_host, active)
                    self._h_occ.observe(int(active.sum()))
                    for i, s in enumerate(pool.slots):
                        if active[i] and s.uid is not None:
                            if self._tiered:
                                s.plane_log.append(lane_planes[i])
                                rec.event(s.uid, obs_trace.DECODE_STEP,
                                          planes=lane_planes[i])
                            else:
                                rec.event(s.uid, obs_trace.DECODE_STEP)
                    if self.policy.paged:
                        used = pool.allocator.used_count
                        live = pool.live_rows()
                        self._h_blocks.observe(used)
                        self._h_rows.observe(live)
                        if used:
                            self._h_frag.observe(
                                1.0 - live / (used * pool.block_size))
                    for ev in self._finished():
                        yield ev
                if not worked and incoming and not queue:
                    # idle gap before the next arrival: fast-forward the
                    # clock.  Only when the queue is empty — a HELD queue
                    # (max-wait batching) must age step by step so the
                    # max_wait deadline fires on time, not at next arrival.
                    now = max(now, incoming[0].arrival - 1)
                now += 1
        finally:
            # An abandoned generator (client disconnect mid-stream, possibly
            # mid-PREFILL) must not leave ghost lanes: free every live lane —
            # including half-prefilled ones, whose staged prompt state dies
            # with the SlotState — so the shared pool is clean for the next
            # call.  Every open span gets its terminal here: a live lane's
            # request is EVICTED (its lane is torn down mid-flight), a
            # request still queued is ABANDONED (never admitted) — so the
            # flight recorder never leaks a span, abandoned or not.
            for i, s in enumerate(pool.slots):
                if s.uid is not None:
                    rec.finish(s.uid, obs_trace.EVICTED,
                               phase=s.phase, filled=s.filled)
                    self._c_req.labels(outcome="evicted").inc()
                    pool.evict(i)
            self._lane_pend.clear()
            for pend in queue:
                # Includes preempted requests waiting to resume — their
                # trace is still open and gets its terminal here.
                if pend.request.uid in rec.active:
                    rec.finish(pend.request.uid, obs_trace.ABANDONED)
                    self._c_req.labels(outcome="abandoned").inc()
            self._g_queue.set(0)
            self._g_progs.labels(kind="decode").set(self.compiled_decode_programs())
            self._g_progs.labels(kind="prefill").set(self.compiled_prefill_programs())
            self._g_progs.labels(kind="admit").set(self.compiled_admit_programs())
            if self.policy.spec_decode:
                self._g_progs.labels(kind="spec").set(self.compiled_spec_programs())

    def _finished(self):
        from .engine import Result

        pool = self.pool
        rec = self.obs.recorder
        per_tok = self.decode_ms_total / max(self.decode_steps, 1)
        for i, s in enumerate(pool.slots):
            if s.uid is not None and s.phase == "decode" and s.remaining <= 0:
                done = pool.evict(i)
                self._lane_pend.pop(i, None)
                # A preempted-and-resumed lane's Result stitches the
                # tokens of its earlier life back in front.
                full = list(done.prior or []) + list(done.tokens)
                plane_log = None
                if self._tiered:
                    plane_log = np.asarray(
                        list(done.prior_planes or []) +
                        list(done.plane_log or []), np.int32)
                rec.finish(done.uid, obs_trace.FINISHED,
                           n_tokens=len(full))
                self._c_req.labels(outcome="finished").inc()
                yield Result(
                    uid=done.uid,
                    tokens=np.asarray(full, np.int32),
                    prefill_ms=done.prefill_ms,
                    decode_ms_per_tok=per_tok,
                    plane_log=plane_log,
                )

    def run(
        self,
        requests: Sequence["repro.serve.engine.Request"],  # noqa: F821
        arrival_steps: Optional[Sequence[int]] = None,
    ) -> List["repro.serve.engine.Result"]:  # noqa: F821
        return list(self.stream(requests, arrival_steps))

    # -- telemetry ---------------------------------------------------------
    def reset_telemetry(self) -> None:
        """Zero the obs bundle (registry + flight recorder) and the scalar
        counters (bench warmup).  Compiled-program caches survive."""
        self.obs.reset()
        self.admit_bursts.clear()
        self.prefill_chunks = 0
        self.decode_ms_total = 0.0
        self.decode_steps = 0
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_committed = 0
        # Degrade-loop control state resets with the telemetry so bench
        # sweeps start every rate from full precision.
        self._shed = 0
        self._calm = 0
        self._preempt_step = 0
        self._preempt_window.clear()
        self._degrade_warned = False
        self.degrade_sheds = 0
        self.degrade_restores = 0
        if self._tiered:
            self._set_plane_gauges()

    def mean_occupancy(self) -> float:
        """Mean fraction of lanes live per decode step (bench metric)."""
        return self._h_occ.mean() / self.pool.n_slots

    def mean_block_occupancy(self) -> float:
        """Mean fraction of pool blocks in use per decode step (paged)."""
        return self._h_blocks.mean() / self.pool.n_blocks if self.pool.n_blocks else 0.0

    def mean_fragmentation(self) -> float:
        """Mean wasted fraction of allocated block rows (paged): the tail
        rows of each lane's last, partially-filled block.  Bounded above
        by ``block_size / (block_size + 1)``; small blocks waste less."""
        return self._h_frag.mean()

    def preemptions_total(self) -> int:
        """Lanes preempted (all tiers) since the last telemetry reset."""
        return int(sum(c.value for _, c in self._c_preempt.children()))

    def degrade_events_total(self) -> int:
        """Shed + restore transitions since the last telemetry reset."""
        return self.degrade_sheds + self.degrade_restores

    def active_planes(self, precision: str = "full") -> int:
        """Current effective plane count for a precision class (tiered
        engines; untiered engines report the packed width or 0)."""
        if not self._tiered:
            return self._n_bits or 0
        return self._effective(precision)

    def spec_accept_rate(self) -> float:
        """Fraction of drafted tokens the full-precision verify accepted
        (spec decode; 0.0 before the first round)."""
        return self.spec_accepted / self.spec_drafted if self.spec_drafted else 0.0
