"""Randomized serve-conformance harness for paged block-table KV.

The contract of ``paged=True``: the slot pool's attention caches become
a global pool of fixed-size blocks plus per-lane block tables, and the
engine must stay *greedy-token-identical* to the bucketed batch-1 oracle
across arbitrary admission/eviction/abandon interleavings while the
:class:`~repro.serve.slots.BlockAllocator` ends every schedule with zero
leaked blocks (free count back to ``n_blocks``, zero committed).

The harness drives seeded random schedules — mixed prompt lengths,
staggered arrivals, lane churn beyond ``n_slots``, periodic mid-stream
abandons — through three engines sharing one request set: the bucketed
oracle, the unpaged chunked-prefill scheduler, and the paged scheduler
(deliberately run with a pool too small for every lane's worst case, so
the block-capacity admission path is exercised, not just the happy
path).  Engines are module-scoped: lane/block state must also survive
schedule after schedule on the SAME pool, which is exactly how a serving
process lives.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import init_params
from repro.obs import trace as obs_trace
from repro.serve import BlockAllocator, Request, SchedulerPolicy, ServeEngine
from repro.serve.slots import SlotPool

N_SEEDS = 25
MAX_LEN = 48
N_SLOTS = 3
BLOCK_SIZE = 4
# Tight pool: 3 lanes x worst-case 5 blocks = 15 > 12, so admission must
# sometimes hold requests on block capacity (commitment check) even when
# a lane is free — the randomized schedules cover both regimes.
N_BLOCKS = 12
# Tighter still for the overcommit harness: commit capacity is
# int(8 * 2.0) = 16 > 8 physical blocks, so admission optimistically
# overfills and the scheduler must preempt mid-flight to make headroom.
OVERCOMMIT_BLOCKS = 8
OVERCOMMIT = 2.0


@pytest.fixture(scope="module")
def granite():
    cfg = reduced_config("granite-3-2b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def oracle(granite):
    cfg, params = granite
    return ServeEngine(params, cfg, max_len=MAX_LEN)


@pytest.fixture(scope="module")
def unpaged(granite):
    cfg, params = granite
    return ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                       policy=SchedulerPolicy(n_slots=N_SLOTS, chunked_prefill=True,
                                              chunk_sizes=(8, 1)))


@pytest.fixture(scope="module")
def paged(granite):
    cfg, params = granite
    return ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                       policy=SchedulerPolicy(n_slots=N_SLOTS, chunked_prefill=True,
                                              chunk_sizes=(8, 1), paged=True,
                                              block_size=BLOCK_SIZE,
                                              n_blocks=N_BLOCKS))


@pytest.fixture(scope="module")
def paged_kernel(granite):
    """Same pool geometry as `paged`, but decode attention runs the
    Pallas block-table-walking kernel (interpret mode off-TPU) instead
    of the jnp full-pool gather."""
    cfg, params = granite
    return ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                       policy=SchedulerPolicy(n_slots=N_SLOTS, chunked_prefill=True,
                                              chunk_sizes=(8, 1), paged=True,
                                              block_size=BLOCK_SIZE,
                                              n_blocks=N_BLOCKS,
                                              paged_kernel=True))


@pytest.fixture(scope="module")
def overcommitted(granite):
    """Paged engine under overcommit pressure: same lane geometry as
    `paged` but a pool too small for even two worst-case lanes, with the
    commitment check doubled — preemption is the only way through."""
    cfg, params = granite
    return ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                       policy=SchedulerPolicy(n_slots=N_SLOTS, chunked_prefill=True,
                                              chunk_sizes=(8, 1), paged=True,
                                              block_size=BLOCK_SIZE,
                                              n_blocks=OVERCOMMIT_BLOCKS,
                                              overcommit=OVERCOMMIT))


def _random_schedule(rng, cfg, n_req=6, max_plen=12, max_new_hi=6):
    """Seeded random workload: mixed prompt lengths, staggered arrivals."""
    reqs = [
        Request(
            uid=i,
            tokens=rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(1, max_plen + 1))).astype(np.int32),
            max_new=int(rng.integers(1, max_new_hi + 1)),
        )
        for i in range(n_req)
    ]
    arrivals = np.cumsum(rng.integers(0, 3, size=n_req)).tolist()
    return reqs, arrivals


_SCHEDULES = {}


def _schedule_and_ref(seed, cfg, oracle):
    """Seeded schedule + its bucketed-oracle greedy reference, computed
    once per seed and shared across the conformance harnesses (the
    overcommit torture replays the exact schedules the paged harness
    serves, so one oracle pass covers both)."""
    if seed not in _SCHEDULES:
        rng = np.random.default_rng(seed)
        reqs, arrivals = _random_schedule(rng, cfg)
        ref = {r.uid: r.tokens for r in oracle.generate(reqs)}
        _SCHEDULES[seed] = (reqs, arrivals, ref)
    return _SCHEDULES[seed]


def _assert_zero_leaks(engine):
    pool = engine.scheduler.pool
    assert pool.allocator.free_count == pool.n_blocks, (
        f"{pool.n_blocks - pool.allocator.free_count} blocks leaked")
    assert pool.allocator.committed == 0
    assert pool.n_active == 0


def _assert_span_accounting(engine):
    """Flight-recorder invariants, cumulative across every schedule this
    module-scoped engine has served: no open (leaked) spans once drained,
    every retired trace carries EXACTLY one terminal event, and a trace
    that finished normally passed through admitted -> first_token."""
    rec = engine.obs.recorder
    assert rec.leaked == [], rec.leaked
    for tr in rec.traces():
        assert tr.terminal_count() == 1, (tr.uid, [e.kind for e in tr.events])
        if tr.terminal.kind == obs_trace.FINISHED:
            assert tr.find(obs_trace.ADMITTED) is not None, tr.uid
            assert tr.find(obs_trace.FIRST_TOKEN) is not None, tr.uid


def _prefill_tokens(engine, path):
    return int(engine.obs.registry.counter(
        "serve_prefill_tokens_total", labels=("path",)).labels(path=path).value)


def _assert_folded(engine):
    """Chunk-1 prefill ran inside the decode dispatch: prompt tokens went
    through the decode program, and no chunk-1 program was ever built."""
    assert _prefill_tokens(engine, "decode") > 0
    assert 1 not in engine.scheduler._chunk_cache


@pytest.mark.conformance
@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_randomized_schedule_conformance(seed, granite, oracle, unpaged, paged,
                                         paged_kernel):
    """One seeded schedule, four engines: greedy tokens must agree
    everywhere (kernel == gather == oracle) and the block pool must
    drain back to full."""
    cfg, _ = granite
    reqs, arrivals, ref = _schedule_and_ref(seed, cfg, oracle)

    out_u = unpaged.generate(reqs, arrival_steps=arrivals)
    assert len(out_u) == len(reqs)
    for r in out_u:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    _assert_span_accounting(unpaged)

    out_p = paged.generate(reqs, arrival_steps=arrivals)
    assert len(out_p) == len(reqs)
    for r in out_p:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    _assert_zero_leaks(paged)
    _assert_span_accounting(paged)

    out_k = paged_kernel.generate(reqs, arrival_steps=arrivals)
    assert len(out_k) == len(reqs)
    for r in out_k:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    _assert_zero_leaks(paged_kernel)
    _assert_span_accounting(paged_kernel)
    # chunk-1 steps fold into the decode dispatch on every engine
    for eng in (unpaged, paged, paged_kernel):
        assert 1 not in eng.scheduler._chunk_cache

    if seed % 5 == 0:
        # mid-stream abandon (client disconnect, lanes possibly
        # mid-prefill): the pool must come back clean — the NEXT seed's
        # run on this same engine is the proof it stayed serviceable
        it = paged.stream(reqs, arrival_steps=arrivals)
        for _ in range(len(reqs) // 2):
            next(it)
        it.close()
        _assert_zero_leaks(paged)
        # teardown must have retired every open span with an
        # evicted/abandoned terminal — never silently dropped
        _assert_span_accounting(paged)
        kinds = {t.terminal.kind for t in paged.obs.recorder.traces()}
        assert kinds & {obs_trace.EVICTED, obs_trace.ABANDONED, obs_trace.FINISHED}


@pytest.mark.conformance
def test_fold_actually_engaged(unpaged, paged, paged_kernel):
    """Meta-check on the module-scoped conformance engines: across the
    seeded schedules prompt tokens really went through the decode
    program (chunk-1 steps), and through chunk programs too."""
    for eng in (unpaged, paged, paged_kernel):
        _assert_folded(eng)
        assert _prefill_tokens(eng, "chunk") > 0


def _tiered(reqs):
    """The harness SLO mix: every 4th uid is latency-tier."""
    return [dataclasses.replace(r, tier="latency" if r.uid % 4 == 0
                                else "throughput") for r in reqs]


def _assert_preemption_lifecycle(engine):
    """Every preempted-then-finished trace must show the full recompute
    lifecycle: each ``preempted`` is followed by a re-``admitted`` and a
    ``re_prefill`` (in that order), every ``re_prefill`` is preceded by
    a ``preempted``, and the trace still reaches ``first_token``."""
    for tr in engine.obs.recorder.traces():
        kinds = [e.kind for e in tr.events]
        if obs_trace.RE_PREFILL in kinds:
            assert kinds.index(obs_trace.PREEMPTED) < kinds.index(
                obs_trace.RE_PREFILL), (tr.uid, kinds)
        if tr.terminal.kind != obs_trace.FINISHED:
            continue  # abandoned/evicted mid-queue: no resume owed
        for i, k in enumerate(kinds):
            if k != obs_trace.PREEMPTED:
                continue
            rest = kinds[i + 1:]
            assert obs_trace.ADMITTED in rest, (tr.uid, kinds)
            assert obs_trace.RE_PREFILL in rest, (tr.uid, kinds)
            assert (rest.index(obs_trace.ADMITTED)
                    < rest.index(obs_trace.RE_PREFILL)), (tr.uid, kinds)
        if obs_trace.PREEMPTED in kinds:
            assert obs_trace.FIRST_TOKEN in kinds, (tr.uid, kinds)


def _preemptions_by_tier(sched):
    return {lbls["tier"]: int(c.value)
            for lbls, c in sched._c_preempt.children()}


@pytest.mark.conformance
@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_randomized_overcommit_preemption_conformance(seed, granite, oracle,
                                                      overcommitted):
    """The preemption torture: the same seeded schedules as the paged
    harness, tiered, through a pool whose commit capacity (16) doubles
    its physical blocks (8) — mid-flight preemption + recompute must
    stay greedy-token-identical to the oracle, drain the allocator
    completely, leak zero spans, and record the full preempted ->
    re-admitted -> re_prefill lifecycle on every resumed trace."""
    cfg, _ = granite
    reqs, arrivals, ref = _schedule_and_ref(seed, cfg, oracle)
    out = overcommitted.generate(_tiered(reqs), arrival_steps=arrivals)
    assert len(out) == len(reqs)
    for r in out:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    _assert_zero_leaks(overcommitted)
    _assert_span_accounting(overcommitted)
    _assert_preemption_lifecycle(overcommitted)

    if seed % 5 == 0:
        # mid-stream abandon while lanes may be preempted/queued for
        # recompute: teardown must still retire every span and return
        # every block — the next seed's clean run is the proof
        it = overcommitted.stream(_tiered(reqs), arrival_steps=arrivals)
        for _ in range(len(reqs) // 2):
            next(it)
        it.close()
        _assert_zero_leaks(overcommitted)
        _assert_span_accounting(overcommitted)


@pytest.mark.conformance
def test_overcommit_torture_actually_preempted(overcommitted):
    """Meta-check on the module-scoped torture engine: across the 25
    seeded schedules the overcommitted pool really did preempt (many
    times), and — victims being drawn throughput-first — the latency
    tier saw at most a sliver of them."""
    sched = overcommitted.scheduler
    total = sched.preemptions_total()
    assert total > 0, "overcommit torture never preempted a lane"
    by_tier = _preemptions_by_tier(sched)
    assert by_tier.get("throughput", 0) > 0, by_tier


def test_forced_preemption_deterministic(granite):
    """Deterministic preemption pin: three 5-block requests on an
    8-block pool with overcommit 2.0 (commit capacity 16 admits all
    three, physical 8 holds one and a bit) — every lane must be
    preempted and recomputed at least once, outputs stay oracle-
    identical, the latency-tier request is never the victim while a
    throughput lane is live, and the allocator drains to zero."""
    cfg, params = granite
    rng = np.random.default_rng(3)
    reqs = [
        Request(uid=i,
                tokens=rng.integers(0, cfg.vocab_size, size=10).astype(np.int32),
                max_new=11,
                tier="latency" if i == 0 else "throughput")
        for i in range(3)
    ]
    ref = {r.uid: r.tokens for r in
           ServeEngine(params, cfg, max_len=MAX_LEN).generate(reqs)}
    eng = ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                      policy=SchedulerPolicy(n_slots=N_SLOTS, chunked_prefill=True,
                                             chunk_sizes=(8, 1), paged=True,
                                             block_size=BLOCK_SIZE,
                                             n_blocks=OVERCOMMIT_BLOCKS,
                                             overcommit=OVERCOMMIT))
    out = eng.generate(reqs)
    assert len(out) == len(reqs)
    for r in out:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    sched = eng.scheduler
    assert sched.preemptions_total() > 0
    by_tier = _preemptions_by_tier(sched)
    # With 3 lanes in one shard, a latency lane can only be the chosen
    # victim once no throughput lane is live — and alone it always fits
    # (up-front rejection bounds lifetime <= physical pool), so it is
    # never preempted in this workload.
    assert by_tier.get("latency", 0) == 0, by_tier
    assert by_tier.get("throughput", 0) == sched.preemptions_total()
    _assert_zero_leaks(eng)
    _assert_span_accounting(eng)
    _assert_preemption_lifecycle(eng)
    kinds = [e.kind for tr in eng.obs.recorder.traces() for e in tr.events]
    assert obs_trace.RE_PREFILL in kinds


@pytest.mark.parametrize("arch", ["gemma3-12b", "recurrentgemma-9b", "mamba2-130m"])
def test_paged_ring_and_recurrent_archs(arch):
    """Ring-buffer (sliding-window) and recurrent (ssm/rglru) state is
    fixed-size per lane and bypasses paging — but it must still ride the
    same scheduler, survive lane churn, and wrap its ring past the
    window while attention neighbours page.  The staggered schedule
    refills a lane while the other decodes, so its prompt runs through
    chunk-1 steps folded into the decode dispatch."""
    cfg = reduced_config(arch)
    params = init_params(jax.random.PRNGKey(1), cfg)
    local = "local" in [k.split("+")[0] for k in cfg.layer_pattern]
    max_new = cfg.window + 4 if local else 8
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(rng.integers(2, 14))).astype(np.int32)
               for _ in range(4)]
    for stagger in (0, 2):
        reqs = [Request(uid=i, tokens=p, max_new=max_new - stagger * i)
                for i, p in enumerate(prompts)]
        ref = {r.uid: r.tokens for r in
               ServeEngine(params, cfg, max_len=64).generate(reqs)}
        eng = ServeEngine(params, cfg, max_len=64, continuous=True,
                          policy=SchedulerPolicy(n_slots=2, chunked_prefill=True,
                                                 chunk_sizes=(8, 1), paged=True,
                                                 block_size=8))
        out = eng.generate(reqs, arrival_steps=[0, 1, 2, 3])
        assert len(out) == len(reqs)
        for r in out:
            np.testing.assert_array_equal(ref[r.uid], r.tokens)
        _assert_zero_leaks(eng)
    _assert_folded(eng)


def test_fold_preempts_mid_prefill_lane(granite):
    """Overcommit preemption of a lane mid-prefill under the fold: a
    throughput lane prefills one token a step inside the decode dispatch
    beside a latency lane's decode, runs the 6-block pool dry, and is
    preempted with no first token yet.  Its resume records TTFT once,
    and both lanes stay oracle-identical."""
    cfg, params = granite
    rng = np.random.default_rng(3)
    reqs = [
        Request(uid=0, tokens=rng.integers(0, cfg.vocab_size, size=10).astype(np.int32),
                max_new=11, tier="latency"),
        Request(uid=1, tokens=rng.integers(0, cfg.vocab_size, size=12).astype(np.int32),
                max_new=6),
    ]
    ref = {r.uid: r.tokens for r in
           ServeEngine(params, cfg, max_len=MAX_LEN).generate(reqs)}
    eng = ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                      policy=SchedulerPolicy(n_slots=2, chunked_prefill=True,
                                             chunk_sizes=(8, 1), paged=True,
                                             block_size=BLOCK_SIZE, n_blocks=6,
                                             overcommit=OVERCOMMIT))
    out = eng.generate(reqs, arrival_steps=[0, 2])
    assert len(out) == len(reqs)
    for r in out:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    tr = {t.uid: t for t in eng.obs.recorder.traces()}
    kinds = [e.kind for e in tr[1].events]
    cut = kinds.index(obs_trace.PREEMPTED)
    assert tr[1].events[cut].attrs["phase"] == "prefill"
    before = [e.attrs["size"] for e in tr[1].events[:cut]
              if e.kind == obs_trace.PREFILL_CHUNK]
    assert before and set(before) == {1}  # folded steps only
    for t in tr.values():
        assert [e.kind for e in t.events].count(obs_trace.FIRST_TOKEN) == 1
    assert eng.obs.registry.histogram("serve_ttft_ms").count == len(reqs)
    _assert_folded(eng)
    _assert_zero_leaks(eng)
    _assert_span_accounting(eng)
    _assert_preemption_lifecycle(eng)


def _fold_engine(granite, chunk_sizes, n_slots=N_SLOTS):
    cfg, params = granite
    return ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                       policy=SchedulerPolicy(n_slots=n_slots, chunked_prefill=True,
                                              chunk_sizes=chunk_sizes, paged=True,
                                              block_size=BLOCK_SIZE))


@pytest.mark.parametrize("chunk_sizes", [(1,), (8, 1)])
def test_fold_counters_keep_their_meaning(granite, chunk_sizes):
    """Prompt tokens are counted once, by the program that consumed them;
    every folded token leaves one size-1 prefill_chunk event; and
    serve_occupancy counts decode-phase lanes only (its sum is the
    decode_step events, none of them a folded lane's)."""
    cfg, _ = granite
    reqs, arrivals = _random_schedule(np.random.default_rng(11), cfg, n_req=6)
    eng = _fold_engine(granite, chunk_sizes)
    out = eng.generate(reqs, arrival_steps=arrivals)
    assert len(out) == len(reqs)
    prompt = sum(len(r.tokens) for r in reqs)
    dec, chk = _prefill_tokens(eng, "decode"), _prefill_tokens(eng, "chunk")
    assert dec + chk == prompt and dec > 0
    events = [e for tr in eng.obs.recorder.traces() for e in tr.events]
    sizes = [e.attrs["size"] for e in events if e.kind == obs_trace.PREFILL_CHUNK]
    assert sum(sizes) == prompt
    assert sizes.count(1) >= dec
    if chunk_sizes == (1,):
        # every prompt token went through the decode program
        assert chk == 0 and eng.scheduler.prefill_chunks == 0
        assert sizes == [1] * dec
    occ = eng.obs.registry.histogram("serve_occupancy")
    assert occ.sum == sum(e.kind == obs_trace.DECODE_STEP for e in events)
    assert occ.count <= eng.scheduler.decode_steps


def test_fold_attn_read_blocks_count_folded_lanes(granite):
    """serve_attn_read_blocks counts the blocks the kernel reads, folded
    lanes' included: one 10-token prompt with max_new=1 is ten folded
    steps reading ceil(rows / 4) blocks each, and no decode lane."""
    cfg, _ = granite
    eng = _fold_engine(granite, (1,), n_slots=2)
    prompt = np.arange(10, dtype=np.int32) % cfg.vocab_size
    [res] = eng.generate([Request(uid=0, tokens=prompt, max_new=1)])
    assert len(res.tokens) == 1
    attn = eng.obs.registry.histogram("serve_attn_read_blocks")
    assert attn.count == eng.scheduler.decode_steps == 10
    assert attn.sum == sum(-(-rows // BLOCK_SIZE) for rows in range(1, 11))
    assert eng.obs.registry.histogram("serve_occupancy").count == 0


def test_admission_blocked_then_unblocked_fifo(granite):
    """The satellite fix: a free LANE is no longer sufficient to admit —
    block capacity gates too, and a blocked head-of-queue request must
    hold the line (FIFO), not be jumped by a smaller one behind it.

    bs=4, n_blocks=4: uid 0 and uid 1 each commit 3 blocks, uid 2 one.
    uid 1 cannot be admitted alongside uid 0 (3 + 3 > 4) even though a
    lane is free, and uid 2 must NOT be admitted in its place (1 would
    fit).  Once uid 0 evicts, uids 1 and 2 admit together."""
    cfg, params = granite
    reqs = [
        Request(uid=0, tokens=np.arange(4, dtype=np.int32), max_new=9),
        Request(uid=1, tokens=(np.arange(4, dtype=np.int32) + 1), max_new=9),
        Request(uid=2, tokens=np.arange(2, dtype=np.int32), max_new=3),
    ]
    ref = {r.uid: r.tokens for r in
           ServeEngine(params, cfg, max_len=32).generate(reqs)}
    eng = ServeEngine(params, cfg, max_len=32, continuous=True, n_slots=2,
                      paged=True, block_size=4, n_blocks=4)
    out = eng.generate(reqs)
    assert len(out) == len(reqs)
    for r in out:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    assert eng.scheduler.admit_bursts == [1, 2], eng.scheduler.admit_bursts
    _assert_zero_leaks(eng)


def test_request_larger_than_pool_rejected(granite):
    """A request whose worst-case block need exceeds the whole pool can
    never be admitted — reject it up front instead of queueing forever."""
    cfg, params = granite
    eng = ServeEngine(params, cfg, max_len=32, continuous=True, n_slots=2,
                      paged=True, block_size=4, n_blocks=2)
    with pytest.raises(ValueError, match="KV blocks"):
        eng.generate([Request(uid=0, tokens=np.arange(8, dtype=np.int32),
                              max_new=8)])


def test_paged_mode_validation(granite):
    cfg, params = granite
    with pytest.raises(ValueError, match="chunked_prefill"):
        SchedulerPolicy(n_slots=2, paged=True)
    with pytest.raises(ValueError, match="continuous"):
        ServeEngine(params, cfg, max_len=32, paged=True)


def test_paged_cache_bytes_scale_with_blocks(granite):
    """The point of the tentpole: cache HBM is n_blocks * block_size
    rows, not n_slots * max_len rows."""
    cfg, _ = granite

    def attn_bytes(pool):
        return sum(
            leaf.nbytes
            for path, leaf in jax.tree_util.tree_flatten_with_path(pool.cache)[0]
            if str(path[-1]).strip(".'\"") in ("k", "v")
        )

    dense = SlotPool(cfg, 4, 64, cache_dtype=np.float32)
    small = SlotPool(cfg, 4, 64, cache_dtype=np.float32, paged=True,
                     block_size=8, n_blocks=8)
    # 4 slots * 64 rows = 256 reserved rows vs 8 blocks * 8 rows = 64
    assert attn_bytes(dense) == 4 * attn_bytes(small)


@pytest.mark.slow
def test_paged_packed_decode_on_2x4_mesh_matches_single_device():
    """Acceptance: paged decode over PACKED weights on a ("data",
    "model") mesh is token-identical to the single-device bucketed
    oracle, with the block pool actually sharded (block axis over data)
    and zero leaked blocks — for both the gather decode path and the
    Pallas kernel path with data-sharded block tables (shard-local pool
    walks under shard_map; the pool is never all-gathered).  Spawned
    with 8 host devices (XLA_FLAGS must precede jax init)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import jax, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.configs import reduced_config
            from repro.core.packing import pack_model_params
            from repro.models import init_params
            from repro.serve import Request, ServeEngine
            cfg = reduced_config("granite-3-2b")
            packed = pack_model_params(init_params(jax.random.PRNGKey(0), cfg), 6)
            mesh = make_mesh((2, 4), ("data", "model"))
            def reqs():
                return [Request(uid=i, tokens=(np.arange(4 + 2 * i, dtype=np.int32) + i)
                                % cfg.vocab_size, max_new=5) for i in range(5)]
            ref = {r.uid: r.tokens
                   for r in ServeEngine(packed, cfg, max_len=32).generate(reqs())}
            for use_kernel in (False, True):
                eng = ServeEngine(packed, cfg, max_len=32, mesh=mesh, continuous=True,
                                  n_slots=4, paged=True, block_size=4, n_blocks=14,
                                  paged_kernel=use_kernel)
                for r in eng.generate(reqs(), arrival_steps=[0, 0, 1, 3, 5]):
                    np.testing.assert_array_equal(ref[r.uid], r.tokens)
                pool = eng.scheduler.pool
                assert pool.allocator.free_count == pool.n_blocks
                assert eng.scheduler.compiled_decode_programs() == 1
                kv = jax.tree.leaves(pool.cache)[0]  # (superblocks, n_blocks, KV, bs, hd)
                assert not kv.sharding.is_fully_replicated, kv.sharding
                assert kv.sharding.spec[1] == "data", kv.sharding.spec
                # block tables co-shard with the pool: lanes over the data
                # axis, one table shard per pool shard (4 % 2 == 14 % 2 == 0)
                assert pool.table_shards == 2, pool.table_shards
                assert pool.block_table.sharding.spec[0] == "data", (
                    pool.block_table.sharding.spec)
            print("PAGED_MESH_OK")
        """)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PAGED_MESH_OK" in out.stdout


@pytest.mark.slow
@pytest.mark.conformance
def test_paged_overcommit_preemption_on_2x4_mesh_matches_single_device():
    """Acceptance: overcommitted admission + recompute preemption on a
    ("data", "model") mesh with PACKED weights stays token-identical to
    the single-device bucketed oracle for both decode paths.  The pool
    (8 blocks over 2 table shards = 4 physical per shard, commit
    capacity 8 per shard) cannot hold any two lanes of this workload at
    once, so every schedule preempts; the allocator must still drain to
    zero on every shard."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import jax, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.configs import reduced_config
            from repro.core.packing import pack_model_params
            from repro.models import init_params
            from repro.serve import Request, ServeEngine
            cfg = reduced_config("granite-3-2b")
            packed = pack_model_params(init_params(jax.random.PRNGKey(0), cfg), 6)
            mesh = make_mesh((2, 4), ("data", "model"))
            def reqs():
                return [Request(uid=i, tokens=(np.arange(4 + 2 * i, dtype=np.int32) + i)
                                % cfg.vocab_size, max_new=5,
                                tier="latency" if i % 4 == 0 else "throughput")
                        for i in range(5)]
            ref = {r.uid: r.tokens
                   for r in ServeEngine(packed, cfg, max_len=32).generate(reqs())}
            for use_kernel in (False, True):
                eng = ServeEngine(packed, cfg, max_len=32, mesh=mesh, continuous=True,
                                  n_slots=4, paged=True, block_size=4, n_blocks=8,
                                  overcommit=2.0, paged_kernel=use_kernel)
                for r in eng.generate(reqs(), arrival_steps=[0, 0, 1, 3, 5]):
                    np.testing.assert_array_equal(ref[r.uid], r.tokens)
                pool = eng.scheduler.pool
                assert pool.table_shards == 2, pool.table_shards
                assert pool.allocator.free_count == pool.n_blocks
                assert pool.allocator.committed == 0
                assert eng.scheduler.preemptions_total() > 0, "never preempted"
                assert not eng.obs.recorder.leaked, eng.obs.recorder.leaked
            print("PAGED_PREEMPT_MESH_OK")
        """)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PAGED_PREEMPT_MESH_OK" in out.stdout


# -- bit-plane speculative decoding --------------------------------------
#
# The spec-decode contract (scheduler._spec_round): drafting from a
# truncated view of the SAME packed weights + greedy full-precision
# verify must stay token-identical to the bucketed oracle across the
# same 25 randomized schedules, while every rejected draft's rows are
# rewound (tail blocks freed) and the pool still drains to zero.  The
# engines run 6-bit packed weights with 2-plane drafts so the verify
# really rejects (a float engine's "drafts" would be exact and the
# rollback path would never fire).

SPEC_BITS = 6
SPEC_DRAFT_PLANES = 2
SPEC_GAMMA = 3


@pytest.fixture(scope="module")
def packed_granite():
    from repro.core.packing import pack_model_params

    cfg = reduced_config("granite-3-2b")
    return cfg, pack_model_params(init_params(jax.random.PRNGKey(0), cfg),
                                  SPEC_BITS)


@pytest.fixture(scope="module")
def packed_oracle(packed_granite):
    cfg, params = packed_granite
    return ServeEngine(params, cfg, max_len=MAX_LEN)


@pytest.fixture(scope="module")
def packed_paged(packed_granite):
    """Non-speculative packed paged engine: the direct baseline the spec
    engine must match token-for-token (spec == non-spec == oracle)."""
    cfg, params = packed_granite
    return ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                       policy=SchedulerPolicy(n_slots=N_SLOTS, chunked_prefill=True,
                                              chunk_sizes=(8, 1), paged=True,
                                              block_size=BLOCK_SIZE,
                                              n_blocks=N_BLOCKS))


@pytest.fixture(scope="module")
def spec(packed_granite):
    cfg, params = packed_granite
    return ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                       policy=SchedulerPolicy(n_slots=N_SLOTS, chunked_prefill=True,
                                              chunk_sizes=(8, 1), paged=True,
                                              block_size=BLOCK_SIZE,
                                              n_blocks=N_BLOCKS,
                                              spec_decode=True,
                                              draft_planes=SPEC_DRAFT_PLANES,
                                              gamma=SPEC_GAMMA))


_SPEC_SCHEDULES = {}


def _spec_schedule_and_ref(seed, cfg, packed_oracle):
    """Same seeded schedules as the paged harness (same rng stream), with
    the PACKED oracle's greedy reference."""
    if seed not in _SPEC_SCHEDULES:
        rng = np.random.default_rng(seed)
        reqs, arrivals = _random_schedule(rng, cfg)
        ref = {r.uid: r.tokens for r in packed_oracle.generate(reqs)}
        _SPEC_SCHEDULES[seed] = (reqs, arrivals, ref)
    return _SPEC_SCHEDULES[seed]


def _assert_spec_round_spans(engine):
    """Spec lanes trade DECODE_STEP for DRAFT/VERIFY pairs: every DRAFT
    is followed by a VERIFY whose committed count is in [1, steps], and
    a ROLLBACK (rejected + freed bookkeeping) only ever follows a
    partial accept."""
    for tr in engine.obs.recorder.traces():
        evs = tr.events
        for i, ev in enumerate(evs):
            if ev.kind == obs_trace.DRAFT:
                assert i + 1 < len(evs) and evs[i + 1].kind == obs_trace.VERIFY, \
                    (tr.uid, [e.kind for e in evs])
                steps = ev.attrs["steps"]
                ver = evs[i + 1].attrs
                assert 0 <= ver["accepted"] <= steps, (tr.uid, ver)
                assert 1 <= ver["committed"] <= steps, (tr.uid, ver)
            if ev.kind == obs_trace.ROLLBACK:
                ver = evs[i - 1]
                assert ver.kind == obs_trace.VERIFY, (tr.uid, [e.kind for e in evs])
                assert ev.attrs["rejected"] > 0
                assert ver.attrs["accepted"] + ev.attrs["rejected"] \
                    == evs[i - 2].attrs["steps"]


@pytest.mark.conformance
@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_randomized_spec_decode_conformance(seed, packed_granite, packed_oracle,
                                            packed_paged, spec):
    """One seeded schedule, three packed engines: speculative decode must
    agree with the non-speculative paged engine AND the bucketed oracle
    token-for-token, drain the block pool, and keep span accounting."""
    cfg, _ = packed_granite
    reqs, arrivals, ref = _spec_schedule_and_ref(seed, cfg, packed_oracle)

    out_n = packed_paged.generate(reqs, arrival_steps=arrivals)
    assert len(out_n) == len(reqs)
    for r in out_n:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    _assert_zero_leaks(packed_paged)
    _assert_span_accounting(packed_paged)

    out_s = spec.generate(reqs, arrival_steps=arrivals)
    assert len(out_s) == len(reqs)
    for r in out_s:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    _assert_zero_leaks(spec)
    _assert_span_accounting(spec)
    _assert_spec_round_spans(spec)

    if seed % 5 == 0:
        # mid-stream abandon while lanes may be mid-round: teardown must
        # still retire every span and return every block (including any
        # granted for drafts that will never be verified)
        it = spec.stream(reqs, arrival_steps=arrivals)
        for _ in range(len(reqs) // 2):
            next(it)
        it.close()
        _assert_zero_leaks(spec)
        _assert_span_accounting(spec)


@pytest.mark.conformance
def test_spec_torture_actually_drafted_and_rejected(spec):
    """Meta-check on the module-scoped spec engine: across the 25 seeded
    schedules the verify really did both accept and reject drafts (the
    2-of-6-plane drafts are coarse enough to miss), so the conformance
    above exercised commit AND rewind, not just the happy path."""
    sched = spec.scheduler
    assert sched.spec_rounds > 0
    assert sched.spec_accepted > 0, "verify never accepted a draft"
    assert sched.spec_drafted > sched.spec_accepted, "verify never rejected"
    assert 0.0 < sched.spec_accept_rate() < 1.0
    assert sched.spec_committed > 0
    kinds = {e.kind for tr in spec.obs.recorder.traces() for e in tr.events}
    assert {obs_trace.DRAFT, obs_trace.VERIFY, obs_trace.ROLLBACK} <= kinds
    # the whole point: one fused program per round depth, not per
    # (depth x precision) — the plane count is a runtime operand
    assert sched.compiled_spec_programs() <= SPEC_GAMMA


def test_spec_decode_under_overcommit_preemption(packed_granite):
    """Preemption can only fire at round setup, so a preempted lane's
    recompute snapshot never contains an unverified draft token: spec
    decode + overcommit 2.0 on a pool too small for two worst-case lanes
    must preempt mid-flight and STILL be token-identical to the oracle,
    with the full preempted -> re-admitted -> re_prefill lifecycle and
    zero leaked blocks."""
    cfg, params = packed_granite
    rng = np.random.default_rng(3)
    reqs = [
        Request(uid=i,
                tokens=rng.integers(0, cfg.vocab_size, size=10).astype(np.int32),
                max_new=11,
                tier="latency" if i == 0 else "throughput")
        for i in range(3)
    ]
    ref = {r.uid: r.tokens for r in
           ServeEngine(params, cfg, max_len=MAX_LEN).generate(reqs)}
    eng = ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                      policy=SchedulerPolicy(n_slots=N_SLOTS, chunked_prefill=True,
                                             chunk_sizes=(8, 1), paged=True,
                                             block_size=BLOCK_SIZE,
                                             n_blocks=OVERCOMMIT_BLOCKS,
                                             overcommit=OVERCOMMIT,
                                             spec_decode=True,
                                             draft_planes=SPEC_DRAFT_PLANES,
                                             gamma=SPEC_GAMMA))
    out = eng.generate(reqs)
    assert len(out) == len(reqs)
    for r in out:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    sched = eng.scheduler
    assert sched.preemptions_total() > 0, "never preempted mid-spec"
    assert sched.spec_rounds > 0
    _assert_zero_leaks(eng)
    _assert_span_accounting(eng)
    _assert_preemption_lifecycle(eng)
    _assert_spec_round_spans(eng)
    kinds = [e.kind for tr in eng.obs.recorder.traces() for e in tr.events]
    assert obs_trace.RE_PREFILL in kinds


def test_spec_decode_mode_validation(packed_granite):
    cfg, params = packed_granite
    with pytest.raises(ValueError, match="paged"):
        SchedulerPolicy(n_slots=2, spec_decode=True)
    with pytest.raises(ValueError, match="continuous"):
        ServeEngine(params, cfg, max_len=32, spec_decode=True)
    eng = ServeEngine(params, cfg, max_len=32, continuous=True, n_slots=2,
                      paged=True, block_size=4, spec_decode=True)
    with pytest.raises(ValueError, match="greedy"):
        eng.generate([Request(uid=0, tokens=np.arange(4, dtype=np.int32),
                              max_new=2, temperature=0.7)])


def test_spec_commit_rewind_never_leaks_blocks():
    """Pool-level accept/reject/rewind property: seeded random spec-round
    sequences (grow to the round's draft demand, commit a random 1..gamma
    prefix, rewind the rest) against a live SlotPool — after every round
    the lane holds EXACTLY the blocks covering its verified rows, and
    admit/round/evict interleavings always drain the allocator to zero."""
    cfg = reduced_config("granite-3-2b")
    rng = np.random.default_rng(0)
    for trial in range(10):
        n_blocks = int(rng.integers(8, 17))
        pool = SlotPool(cfg, 3, MAX_LEN, cache_dtype=np.float32, paged=True,
                        block_size=BLOCK_SIZE, n_blocks=n_blocks)
        alloc = pool.allocator
        uid = 0
        for _ in range(40):
            kind = int(rng.integers(0, 3))
            free = pool.free_slots()
            if kind == 0 and free:  # admit + (simulated) prefill
                plen = int(rng.integers(1, 9))
                max_new = int(rng.integers(1, 9))
                need = alloc.blocks_for_rows(plen + max_new - 1)
                if alloc.committed + need > alloc.commit_capacity:
                    continue
                slot = free[0]
                pool.admit(slot, uid, np.arange(plen, dtype=np.int32),
                           max_new, 0.0, now=0, wall=0.0)
                uid += 1
                # chunked prefill lands rows [0, plen), emits the first
                # token -> steady state: g=1, row plen-1+1 unwritten
                pool.grow_rows(slot, plen)
                s = pool.slots[slot]
                s.phase, s.tokens, s.remaining = "decode", [0], max_new - 1
            elif kind == 1:  # one spec round on a random decoding lane
                lanes = [i for i in range(pool.n_slots)
                         if pool.slots[i].uid is not None
                         and pool.slots[i].remaining > 0]
                if not lanes:
                    continue
                slot = lanes[int(rng.integers(0, len(lanes)))]
                s = pool.slots[slot]
                plen, g = len(s.prompt), len(s.tokens)
                gam = int(rng.integers(1, min(SPEC_GAMMA, s.remaining) + 1))
                pool.grow_many({slot: plen + g + gam - 1})
                c = int(rng.integers(1, gam + 1))  # accepted prefix (+corr)
                pool.commit_spec(
                    slot, rng.integers(0, cfg.vocab_size, size=c).tolist())
                assert len(s.blocks) == alloc.blocks_for_rows(
                    plen + len(s.tokens) - 1), (trial, slot)
                if s.remaining == 0:
                    pool.evict(slot)
            elif kind == 2:  # preempt/abandon mid-flight
                live = [i for i in range(pool.n_slots)
                        if pool.slots[i].uid is not None]
                if live:
                    pool.evict(live[int(rng.integers(0, len(live)))])
        for i in range(pool.n_slots):
            if pool.slots[i].uid is not None:
                pool.evict(i)
        assert alloc.free_count == n_blocks, trial
        assert alloc.committed == 0, trial


@pytest.mark.slow
@pytest.mark.conformance
def test_spec_decode_on_2x4_mesh_matches_single_device():
    """Acceptance: speculative decode over PACKED weights on a ("data",
    "model") mesh — the fused draft-scan + verify program runs shard_map'd
    with the plane count as a replicated runtime scalar — stays
    token-identical to the single-device bucketed oracle, with the block
    pool sharded and drained."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import jax, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.configs import reduced_config
            from repro.core.packing import pack_model_params
            from repro.models import init_params
            from repro.serve import Request, ServeEngine
            cfg = reduced_config("granite-3-2b")
            packed = pack_model_params(init_params(jax.random.PRNGKey(0), cfg), 6)
            mesh = make_mesh((2, 4), ("data", "model"))
            def reqs():
                return [Request(uid=i, tokens=(np.arange(4 + 2 * i, dtype=np.int32) + i)
                                % cfg.vocab_size, max_new=5) for i in range(5)]
            ref = {r.uid: r.tokens
                   for r in ServeEngine(packed, cfg, max_len=32).generate(reqs())}
            eng = ServeEngine(packed, cfg, max_len=32, mesh=mesh, continuous=True,
                              n_slots=4, paged=True, block_size=4, n_blocks=14,
                              spec_decode=True, draft_planes=2, gamma=3)
            for r in eng.generate(reqs(), arrival_steps=[0, 0, 1, 3, 5]):
                np.testing.assert_array_equal(ref[r.uid], r.tokens)
            sched = eng.scheduler
            pool = sched.pool
            assert pool.allocator.free_count == pool.n_blocks
            assert pool.allocator.committed == 0
            assert pool.table_shards == 2, pool.table_shards
            assert sched.spec_rounds > 0
            assert sched.spec_committed > 0
            print("SPEC_MESH_OK")
        """)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SPEC_MESH_OK" in out.stdout


def test_overcommit_preemption_randomized_interleavings():
    """Non-hypothesis twin of test_property.py's overcommit interleaving
    test (hypothesis is an optional dep): seeded random admit/grow/finish
    sequences against the overcommitted allocator, mirroring the
    scheduler's discipline — whenever a grow must preempt, a victim
    exists (no deadlock), a latency-tier lane is never the victim while
    a throughput-tier candidate is live, blocks are never double-
    assigned, and everything drains to zero."""
    from types import SimpleNamespace

    from repro.serve.scheduler import preemption_order

    rng = np.random.default_rng(0)
    for trial in range(40):
        n_slots = int(rng.integers(2, 6))
        n_blocks = int(rng.integers(2, 21))
        a = BlockAllocator(n_blocks, 4,
                           overcommit=float(rng.uniform(1.0, 3.0)))
        lanes, live_blocks, admit_seq = {}, set(), 0

        def preempt(slot):
            lane = lanes.pop(slot)
            live_blocks.difference_update(lane.blocks)
            if lane.blocks:
                a.free(lane.blocks)
            a.release(lane.lifetime)

        for _ in range(60):
            kind = int(rng.integers(0, 3))
            if kind == 0 and len(lanes) < n_slots:  # admit
                lifetime = int(rng.integers(1, n_blocks + 1))
                if not a.reserve(lifetime):
                    assert a.committed + lifetime > a.commit_capacity
                    continue
                slot = next(s for s in range(n_slots) if s not in lanes)
                admit_seq += 1
                lanes[slot] = SimpleNamespace(
                    tier="latency" if rng.integers(0, 4) == 0 else "throughput",
                    admit_seq=admit_seq, lifetime=lifetime, blocks=[])
            elif kind == 1 and lanes:  # grow one lane by one block
                slot = sorted(lanes)[int(rng.integers(0, len(lanes)))]
                lane = lanes[slot]
                if len(lane.blocks) >= lane.lifetime:
                    continue
                for _ in range(n_slots + 1):
                    got = a.alloc(1, owner=slot)
                    if got is not None:
                        assert not set(got) & live_blocks
                        live_blocks.update(got)
                        lane.blocks.extend(got)
                        break
                    cands = [(s, l) for s, l in lanes.items()
                             if l.blocks or s == slot]
                    assert len(cands) >= 2, "headroom deadlock"
                    victim_slot, victim = preemption_order(cands)[0]
                    if victim.tier == "latency":
                        assert all(l.tier == "latency" for _, l in cands)
                    preempt(victim_slot)
                    if victim_slot == slot:
                        break
                else:
                    raise AssertionError("headroom loop did not terminate")
            elif kind == 2 and lanes:  # finish
                preempt(sorted(lanes)[int(rng.integers(0, len(lanes)))])

        for slot in sorted(lanes):
            preempt(slot)
        assert a.free_count == n_blocks, trial
        assert a.committed == 0, trial
        assert not live_blocks, trial


def test_block_allocator_randomized_interleavings():
    """Non-hypothesis twin of the property test (hypothesis is an
    optional dep): seeded random alloc/free interleavings never
    double-assign a block, and — blocks being interchangeable through
    the table indirection — an allocation fails ONLY when the pool
    genuinely lacks that many free blocks (no stranding by
    fragmentation)."""
    rng = np.random.default_rng(0)
    for _ in range(50):
        n_blocks = int(rng.integers(1, 32))
        a = BlockAllocator(n_blocks, int(rng.integers(1, 16)))
        live = []
        for _ in range(40):
            if rng.random() < 0.55:
                k = int(rng.integers(0, n_blocks + 2))
                got = a.alloc(k)
                if k <= n_blocks - len(live):
                    assert got is not None and len(got) == k
                    assert len(set(got)) == k  # no dup within a grant
                    assert not set(got) & set(live)  # never a live block
                    assert all(0 <= b < n_blocks for b in got)
                    live.extend(got)
                else:
                    assert got is None  # and ONLY then
            elif live:
                j = int(rng.integers(1, len(live) + 1))
                out, live = live[:j], live[j:]
                a.free(out)
        assert a.free_count == n_blocks - len(live)
        if live:
            a.free([live[0]])
            with pytest.raises(ValueError, match="double free"):
                a.free([live[0]])
            live.pop(0)


# ---------------------------------------------------------------------------
# Precision-tier degrade conformance (serve-time plane shedding)
# ---------------------------------------------------------------------------

DEGRADE_ECONOMY_PLANES = 4


@pytest.fixture(scope="module")
def degrade_paged(packed_granite):
    """Tiered paged engine with the degrade loop armed: the conformance
    harness drives plane switches on exact per-seed schedules via the
    ``force_shed`` hook, so every lane decodes through mid-stream
    precision transitions — same pool geometry as `packed_paged`."""
    cfg, params = packed_granite
    return ServeEngine(params, cfg, max_len=MAX_LEN, continuous=True,
                       policy=SchedulerPolicy(n_slots=N_SLOTS, chunked_prefill=True,
                                              chunk_sizes=(8, 1), paged=True,
                                              block_size=BLOCK_SIZE,
                                              n_blocks=N_BLOCKS,
                                              precision_tiers={
                                                  "economy": DEGRADE_ECONOMY_PLANES},
                                              degrade=True))


@pytest.mark.conformance
@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_randomized_degrade_conformance(seed, packed_granite, degrade_paged):
    """One seeded schedule with mixed precision classes and a forced
    deterministic shed/restore schedule: every emitted token must equal
    the STATIC-truncation replay of that lane's ``plane_log``
    (obs.quality.replay_plane_log — a different param tree and compiled
    program per plane count, KV carried across every switch), the block
    pool must drain back to full, and span accounting must balance.
    This is the token-consistency acceptance for mid-stream plane
    switching: runtime plane dispatch == static truncation, per token."""
    from repro.obs.quality import replay_plane_log

    cfg, params = packed_granite
    rng = np.random.default_rng(seed)
    reqs, arrivals = _random_schedule(rng, cfg)
    reqs = [dataclasses.replace(
                r, precision="economy" if rng.integers(2) else "full")
            for r in reqs]
    sched = degrade_paged.scheduler
    # deterministic per-seed sawtooth: hold each shed level for `period`
    # steps, cycling 0..amp-1 — both shed and restore transitions fire
    period = int(rng.integers(2, 5))
    amp = int(rng.integers(2, 5))
    sched.force_shed = lambda step: (step // period) % amp
    try:
        out = degrade_paged.generate(reqs, arrival_steps=arrivals)
    finally:
        sched.force_shed = None
    assert len(out) == len(reqs)
    prompts = {r.uid: r.tokens for r in reqs}
    for r in out:
        assert r.plane_log is not None and len(r.plane_log) == len(r.tokens), r.uid
        assert r.plane_log[0] == SPEC_BITS, "prefill must run at full precision"
        replay = replay_plane_log(params, cfg, prompts[r.uid], r.plane_log,
                                  MAX_LEN)
        np.testing.assert_array_equal(replay, r.tokens)
    _assert_zero_leaks(degrade_paged)
    _assert_span_accounting(degrade_paged)

    if seed % 5 == 0:
        # mid-stream abandon while planes are shed: teardown must retire
        # every span and return every block, and the degrade state must
        # not pin the NEXT schedule's lanes at a stale shed level
        sched.force_shed = lambda step: 2
        try:
            it = degrade_paged.stream(reqs, arrival_steps=arrivals)
            for _ in range(len(reqs) // 2):
                next(it)
            it.close()
        finally:
            sched.force_shed = None
        _assert_zero_leaks(degrade_paged)
        _assert_span_accounting(degrade_paged)


@pytest.mark.conformance
def test_degrade_torture_actually_switched(degrade_paged):
    """Meta-check on the module-scoped degrade engine: across the seeded
    schedules the forced schedules really did shed AND restore planes
    (sawtooths with amp 1 never switch), and the runtime plane dispatch
    never forked the single pooled decode program."""
    sched = degrade_paged.scheduler
    kinds = {e.kind for tr in degrade_paged.obs.recorder.traces()
             for e in tr.events}
    assert obs_trace.PLANES_SHED in kinds, "no shed transition ever fired"
    assert obs_trace.PLANES_RESTORED in kinds, "no restore ever fired"
    # plane counts and degrade transitions are runtime operands, never a
    # recompile: ONE pooled decode program, total
    assert sched.compiled_decode_programs() == 1
