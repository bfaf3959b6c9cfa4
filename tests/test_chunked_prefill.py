"""Chunked prefill through the slot pool.

The contract: with ``chunked_prefill=True`` the continuous scheduler
must stay greedy-token-identical to the bucketed batch-1 reference —
across mixed prompt lengths, staggered arrivals, lane reuse, multi-chunk
prompts, ring-buffer wraps and recurrent state carried over chunk
boundaries — while the prefill compiled-program set stays bounded by the
chunk-size table instead of growing with the number of distinct prompt
lengths, and admission fuses every placeable request into one dispatch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import init_params
from repro.obs import trace as obs_trace
from repro.serve import Request, SchedulerPolicy, ServeEngine


@pytest.fixture(scope="module")
def granite():
    cfg = reduced_config("granite-3-2b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _mixed_requests(cfg, n=6, max_new=6):
    lens = [4, 7, 4, 10, 6, 9]
    return [
        Request(uid=i, tokens=(np.arange(lens[i % len(lens)], dtype=np.int32)
                               * (i + 2)) % cfg.vocab_size,
                max_new=max_new + (i % 3))
        for i in range(n)
    ]


def _reference(params, cfg, reqs, max_len=64):
    return {r.uid: r.tokens for r in
            ServeEngine(params, cfg, max_len=max_len).generate(reqs)}


def _chunk_sizes_by_request(eng):
    """Each request's prefill chunks, in order, from the flight recorder."""
    return {tr.uid: [e.attrs["size"] for e in tr.events
                     if e.kind == obs_trace.PREFILL_CHUNK]
            for tr in eng.obs.recorder.traces()}


def _prefill_tokens(eng, path):
    return int(eng.obs.registry.counter(
        "serve_prefill_tokens_total", labels=("path",)).labels(path=path).value)


def _assert_folded(eng):
    """Chunk-1 prefill ran inside the decode dispatch: prompt tokens went
    through the decode program, and no chunk-1 program was ever built."""
    assert _prefill_tokens(eng, "decode") > 0
    assert 1 not in eng.scheduler._chunk_cache


def test_chunked_mixed_lengths_staggered_token_identical(granite):
    cfg, params = granite
    reqs = _mixed_requests(cfg)
    ref = _reference(params, cfg, reqs)
    eng = ServeEngine(params, cfg, max_len=64, continuous=True, n_slots=4,
                      chunked_prefill=True)
    out = eng.generate(reqs, arrival_steps=[0, 0, 2, 3, 7, 11])
    assert len(out) == len(reqs)
    for r in out:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    assert eng.scheduler.compiled_decode_programs() == 1
    _assert_folded(eng)


def test_multi_chunk_prompts_and_lane_reuse(granite):
    """Prompts longer than the largest chunk span several prefill
    dispatches, interleaved with decode steps of earlier lanes; more
    requests than lanes forces evict+refill of half-stale lanes."""
    cfg, params = granite
    reqs = _mixed_requests(cfg, n=7)
    ref = _reference(params, cfg, reqs)
    eng = ServeEngine(params, cfg, max_len=64, continuous=True,
                      policy=SchedulerPolicy(n_slots=2, chunked_prefill=True,
                                             chunk_sizes=(4, 1)))
    out = eng.generate(reqs)
    assert len(out) == len(reqs)
    for r in out:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    # chunk 4 over prompts up to 10 tokens => several multi-chunk prefills:
    # chunk-4 programs, then chunk-1 steps folded into the decode dispatch
    chunks = _chunk_sizes_by_request(eng)
    assert sum(len(c) for c in chunks.values()) > len(reqs)
    assert any(4 in c and 1 in c for c in chunks.values())
    _assert_folded(eng)


@pytest.mark.parametrize("arch", ["gemma3-12b", "recurrentgemma-9b", "mamba2-130m"])
def test_chunked_ring_and_recurrent_archs(arch):
    """Ring-buffer (sliding-window) caches and recurrent (rglru/ssm)
    state must survive chunk boundaries: chunks smaller than the prompt
    carry conv tails + hidden state; a chunk larger than the ring (C=32 >
    Wc=16 for gemma3) exercises the concat-attend + gather-rebuild path;
    decoding past the window wraps each lane's ring at a different
    offset."""
    cfg = reduced_config(arch)
    params = init_params(jax.random.PRNGKey(1), cfg)
    max_new = cfg.window + 4 if "local" in [k.split("+")[0] for k in cfg.layer_pattern] else 8
    reqs = [
        Request(uid=i, tokens=(np.arange(4 + 5 * i, dtype=np.int32) + i)
                % cfg.vocab_size, max_new=max_new)
        for i in range(4)
    ]
    ref = _reference(params, cfg, reqs)
    for sizes in [(8, 4, 1), (32, 1)]:
        eng = ServeEngine(params, cfg, max_len=64, continuous=True,
                          policy=SchedulerPolicy(n_slots=2, chunked_prefill=True,
                                                 chunk_sizes=sizes))
        out = eng.generate(reqs, arrival_steps=[0, 1, 2, 3])
        assert len(out) == len(reqs)
        for r in out:
            np.testing.assert_array_equal(ref[r.uid], r.tokens)
        # chunk-1 steps ran the decode program (recurrent state carried
        # one token at a time, ring slots written at the fill position)
        _assert_folded(eng)
        assert eng.scheduler.compiled_decode_programs() == 1


def test_prefill_program_count_bounded(granite):
    """The satellite contract: across 20 distinct prompt lengths the
    chunked path compiles <= len(chunk_sizes) + 1 prefill programs while
    the legacy path compiles one per length."""
    cfg, params = granite
    n = 20
    reqs = [Request(uid=i, tokens=(np.arange(2 + i, dtype=np.int32) * 3)
                    % cfg.vocab_size, max_new=1)
            for i in range(n)]
    sizes = (16, 4, 1)
    chunked = ServeEngine(params, cfg, max_len=64, continuous=True,
                          policy=SchedulerPolicy(n_slots=4, chunked_prefill=True,
                                                 chunk_sizes=sizes))
    chunked.generate(reqs)
    assert chunked.scheduler.compiled_prefill_programs() <= len(sizes) + 1
    assert chunked.scheduler.compiled_admit_programs() == 1
    legacy = ServeEngine(params, cfg, max_len=64, continuous=True, n_slots=4)
    legacy.generate(reqs)
    assert legacy.scheduler.compiled_prefill_programs() == n


def _pick(chunk_sizes, max_remaining, n_decoding, n_slots=8, occupancy=True):
    """Drive ContinuousScheduler._pick_chunk without an engine: it reads
    only policy.chunk_sizes/occupancy_chunking and pool.n_slots."""
    from types import SimpleNamespace

    from repro.serve.scheduler import ContinuousScheduler

    fake = SimpleNamespace(
        policy=SimpleNamespace(chunk_sizes=chunk_sizes,
                               occupancy_chunking=occupancy),
        pool=SimpleNamespace(n_slots=n_slots),
    )
    return ContinuousScheduler._pick_chunk(fake, max_remaining, n_decoding)


def test_chunk_picker_monotone_in_occupancy():
    """The occupancy-aware picker: always a configured size (the
    compiled set stays bounded by the table), monotone non-increasing as
    more lanes decode, the legacy smallest-covering rule when the pool
    is idle, and the smallest size at full decode occupancy."""
    sizes = (128, 32, 8, 1)
    for remaining in (1, 5, 40, 200):
        picks = [_pick(sizes, remaining, d) for d in range(9)]
        assert all(p in sizes for p in picks), picks
        assert all(a >= b for a, b in zip(picks, picks[1:])), (remaining, picks)
        cover = next((c for c in sorted(sizes) if c >= remaining), max(sizes))
        assert picks[0] == cover, (remaining, picks)
        assert picks[-1] == min(cover, min(sizes)), (remaining, picks)


def test_chunk_picker_off_restores_static_rule(granite):
    """occupancy_chunking=False is the exact legacy behaviour: the
    smallest covering chunk regardless of decode occupancy — and the
    engine under that flag still matches the oracle."""
    sizes = (128, 32, 1)
    for d in range(9):
        assert _pick(sizes, 200, d, occupancy=False) == 128
        assert _pick(sizes, 20, d, occupancy=False) == 32
        assert _pick(sizes, 1, d, occupancy=False) == 1
    cfg, params = granite
    reqs = _mixed_requests(cfg)
    ref = _reference(params, cfg, reqs)
    eng = ServeEngine(params, cfg, max_len=64, continuous=True,
                      policy=SchedulerPolicy(n_slots=3, chunked_prefill=True,
                                             chunk_sizes=(8, 1),
                                             occupancy_chunking=False))
    for r in eng.generate(reqs, arrival_steps=[0, 0, 1, 2, 4, 6]):
        np.testing.assert_array_equal(ref[r.uid], r.tokens)


def test_chunk_picker_compile_set_stays_bounded(granite):
    """Occupancy chunking picks VARYING sizes across a staggered
    workload, but every pick comes from the table, so the compiled
    prefill set keeps the len(chunk_sizes) + 1 bound the static rule
    had."""
    cfg, params = granite
    n = 12
    reqs = [Request(uid=i, tokens=(np.arange(2 + 2 * i, dtype=np.int32) * 3)
                    % cfg.vocab_size, max_new=4)
            for i in range(n)]
    sizes = (16, 4, 1)
    eng = ServeEngine(params, cfg, max_len=64, continuous=True,
                      policy=SchedulerPolicy(n_slots=4, chunked_prefill=True,
                                             chunk_sizes=sizes))
    ref = _reference(params, cfg, reqs)
    # staggered arrivals so prefill chunks interleave live decode lanes
    # (n_decoding > 0) and the occupancy path actually engages
    for r in eng.generate(reqs, arrival_steps=list(range(0, 2 * n, 2))):
        np.testing.assert_array_equal(ref[r.uid], r.tokens)
    assert eng.scheduler.compiled_prefill_programs() <= len(sizes) + 1


def test_multi_admit_fuses_bursts(granite):
    """Every placeable queued request must claim its lane in ONE admission
    dispatch, not one prefill at a time."""
    cfg, params = granite
    reqs = _mixed_requests(cfg, n=4)
    eng = ServeEngine(params, cfg, max_len=64, continuous=True, n_slots=4,
                      chunked_prefill=True)
    out = eng.generate(reqs)  # all arrive at step 0, all 4 lanes free
    assert len(out) == len(reqs)
    assert eng.scheduler.admit_bursts == [4]


def test_scatter_slots_matches_sequential_scatter(granite):
    """The vectorised k-lane scatter must equal k sequential scatter_slot
    calls, with out-of-range padding entries dropped."""
    from repro.models import init_cache, prefill
    from repro.serve import SlotPool, scatter_slot, scatter_slots

    cfg, params = granite
    pool_a = init_cache(cfg, 4, 32, jnp.float32)
    pool_b = jax.tree.map(jnp.copy, pool_a)
    parts = []
    for i in range(2):
        _, part = prefill(params, {"tokens": jnp.arange(5 + i, dtype=jnp.int32)[None]},
                          cfg, 32, cache_dtype=jnp.float32)
        parts.append(part)
    # third fragment is a sentinel riding on the OOB padding slot: if the
    # drop convention broke, its 7s would land somewhere in the pool
    parts.append(jax.tree.map(lambda a: jnp.full_like(a, 7), parts[0]))

    def lane_axis(path):  # blocks leaves carry a leading superblock axis
        return 1 if str(getattr(path[0], "key", path[0])).strip(".'\"") == "blocks" else 0

    stacked = jax.tree_util.tree_map_with_path(
        lambda path, *xs: jnp.concatenate(xs, axis=lane_axis(path)), *parts
    )
    out_a = scatter_slots(pool_a, stacked, jnp.asarray([3, 1, 4], jnp.int32))
    for slot, part in zip((3, 1), parts[:2]):
        pool_b = scatter_slot(pool_b, part, jnp.int32(slot))
    for a, b in zip(jax.tree.leaves(out_a), jax.tree.leaves(pool_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_idle_lane_state_stays_frozen():
    """The active mask must stop inactive lanes from integrating garbage
    recurrent state during pooled decode steps (satellite: keeps state
    finite under long idle).  With one live lane, the three idle lanes'
    ssm/rglru state must still be exactly the zeros they were admitted
    with once the workload drains."""
    cfg = reduced_config("recurrentgemma-9b")
    params = init_params(jax.random.PRNGKey(3), cfg)
    eng = ServeEngine(params, cfg, max_len=64, continuous=True, n_slots=4,
                      chunked_prefill=True)
    [res] = eng.generate([Request(uid=0, tokens=np.arange(6, dtype=np.int32),
                                  max_new=20)])
    assert len(res.tokens) == 20
    pool = eng.scheduler.pool
    idle = [1, 2, 3]

    def assert_idle_zero(path, leaf):
        name = str(path[-1])
        if "state" in name or "conv" in name:
            arr = np.asarray(leaf)
            # slot axis is 1 under blocks (leading superblock axis), 0 else
            lanes = arr[:, idle] if "blocks" in str(path[0]) else arr[idle]
            assert np.all(lanes == 0), (path, np.abs(lanes).max())

    jax.tree_util.tree_map_with_path(assert_idle_zero, pool.cache)


def test_abandoned_stream_mid_prefill_frees_lanes(granite):
    """A stream abandoned while a lane is still consuming prompt chunks
    (client disconnect mid-prefill) must free that lane cleanly — no
    ghost prefill state leaking into the next workload."""
    cfg, params = granite
    eng = ServeEngine(params, cfg, max_len=64, continuous=True,
                      policy=SchedulerPolicy(n_slots=2, chunked_prefill=True,
                                             chunk_sizes=(2, 1)))
    long_prompt = np.arange(12, dtype=np.int32) % cfg.vocab_size
    it = eng.stream([
        Request(uid=0, tokens=np.arange(2, dtype=np.int32), max_new=1),
        Request(uid=1, tokens=long_prompt, max_new=8),
    ])
    first = next(it)  # uid 0 finishes at its first token; uid 1 mid-prefill
    assert first.uid == 0
    pool = eng.scheduler.pool
    assert pool.slots[pool.prefilling()[0]].uid == 1 if pool.prefilling() else True
    it.close()  # abandon: request 1 still consuming chunks
    assert pool.n_active == 0
    assert pool.prefilling() == []
    assert not any(s.prompt is not None for s in pool.slots)
    # the pool must serve the next workload exactly
    reqs = _mixed_requests(cfg, n=3)
    ref = _reference(params, cfg, reqs)
    for r in eng.generate(reqs):
        np.testing.assert_array_equal(ref[r.uid], r.tokens)


def test_chunked_greedy_lane_unaffected_by_hot_lane(granite):
    """Per-slot temperature still holds under chunked admission: a greedy
    lane pooled with a hot lane keeps its exact greedy output."""
    cfg, params = granite
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    [solo] = ServeEngine(params, cfg, max_len=32).generate(
        [Request(uid=0, tokens=prompt, max_new=6)])
    eng = ServeEngine(params, cfg, max_len=32, seed=7, continuous=True, n_slots=2,
                      chunked_prefill=True)
    out = {r.uid: r for r in eng.generate([
        Request(uid=0, tokens=prompt.copy(), max_new=6, temperature=5.0),
        Request(uid=1, tokens=prompt.copy(), max_new=6, temperature=0.0),
    ])}
    np.testing.assert_array_equal(out[1].tokens, solo.tokens)


def test_chunked_rejects_invalid_workloads(granite):
    cfg, params = granite
    eng = ServeEngine(params, cfg, max_len=8, continuous=True, n_slots=2,
                      chunked_prefill=True)
    with pytest.raises(ValueError, match="max_len"):
        eng.generate([Request(uid=0, tokens=np.arange(6, dtype=np.int32), max_new=8)])
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate([Request(uid=0, tokens=np.zeros((0,), np.int32), max_new=2)])
    with pytest.raises(ValueError, match="chunk_sizes"):
        SchedulerPolicy(n_slots=2, chunked_prefill=True, chunk_sizes=())
