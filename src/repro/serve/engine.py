"""Serving engine over BSQ-quantised (packed) weights.

Two scheduling modes share one engine:

* **Continuous batching** (``continuous=True``): requests stream through
  a fixed-capacity slot pool (:mod:`repro.serve.scheduler` /
  :mod:`repro.serve.slots`).  The decode cache is allocated once per
  engine as ``n_slots`` persistent lanes; admission prefills a request
  directly into a free lane (jit-stable scatter) and eviction frees the
  lane mid-flight for the next request.  A per-slot position vector
  drives ONE compiled decode program regardless of prompt lengths or
  arrival pattern — the per-slot positions this module's docstring once
  deferred to "production continuous batching" are now the
  implementation.  With ``chunked_prefill=True`` prompts additionally
  stream through the pooled program in fixed-size chunks (fused
  multi-admit, prefill interleaved with decode, compiled prefill set
  bounded by the chunk-size table) — see the scheduler docstring.  With
  ``paged=True`` (implies chunked prefill) the pool's attention caches
  are a global block pool + per-lane block tables (``serve.slots``), so
  cache HBM scales with live tokens instead of ``n_slots * max_len``;
  ``block_size`` / ``n_blocks`` size the pool.
* **Length-bucketing** (default, the fallback mode): requests ->
  length-bucketed batches -> jitted prefill -> jitted decode loop with a
  single scalar position shared by the bucket.  One compiled program per
  (prompt_len, batch) shape; kept for offline batch jobs where every
  request is present up front and uniform.

Weights arrive either as plain float params or as a BSQ export
(``core.export_packed`` / ``core.export_packed_sharded``, or
``core.packing.pack_model_params``): packed weights are dequantised on
the fly by ``kernels.ops.bitserial_matmul`` (Pallas on TPU, fused-unpack
XLA ref path elsewhere), with the per-group scale row applied in the
kernel epilogue, so HBM reads scale with the *mixed-precision* bit count
— the serving-side payoff of the paper's compression (DESIGN.md §3.2).
Mixed workloads only realise that payoff when lanes stay busy, which is
exactly what the slot pool buys over bucketing.

Sharding: with a ``mesh``, params, the decode cache and the slot pool
are placed under the dist-layer rules (``dist.sharding``:
``tree_param_specs`` / ``cache_tree_specs`` / ``slot_pool_specs``) — the
engine then runs as a real ("data", "model") SPMD program instead of
single-device.  Packed weights are model-parallel too: their
planes/sign/scale leaves follow the base weight's layout, each
PackedWeight is stamped with its mesh axes
(``dist.sharding.annotate_packed_specs``), and every jitted program
traces under ``models.common.packed_shard_mesh`` so the bitserial
matmul runs shard_map'd — per-shard packed bytes, psum-stitched
contraction (per-device packed HBM drops by the model-axis factor).
All layout decisions live in :mod:`repro.dist`; this module only asks
for shardings.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..dist import sharding as dist_sharding
from ..models import transformer
from ..models.common import packed_shard_mesh
from ..obs import Observability
from ..obs import trace as obs_trace


@dataclasses.dataclass
class Request:
    uid: int
    tokens: np.ndarray  # (S,) int32 prompt
    max_new: int = 32
    temperature: float = 0.0  # 0 => greedy
    # SLO class (continuous scheduler): "latency" requests outrank
    # "throughput" at admission and are preempted last under overcommit
    # pressure; the bucketed engine ignores the field.
    tier: str = "throughput"
    # Precision class (continuous scheduler with precision tiers):
    # "full", a key of the policy's precision_tiers table, or an
    # explicit active-plane count (int) — validated at stream() like
    # ``tier``.  The bucketed engine ignores the field; an untiered
    # continuous engine rejects anything but "full".
    precision: object = "full"


@dataclasses.dataclass
class Result:
    uid: int
    tokens: np.ndarray
    # TTFT under the one definition every path shares: the request's
    # admitted -> first_token span (obs.trace.RequestTrace.ttft_ms).
    prefill_ms: float
    # Tiered engines only: per-token active bit-plane count each token
    # was computed at, parallel to ``tokens`` (prefill's first token at
    # full precision, decode tokens at the step's effective count after
    # any degrade shed).  None on untiered paths.  The token-identity
    # oracle replays this log against static plane truncation.
    plane_log: Optional[np.ndarray] = None


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig, max_len: int = 4096, seed: int = 0,
                 mesh=None, continuous: bool = False, n_slots: int = 8,
                 policy: Optional["SchedulerPolicy"] = None,
                 chunked_prefill: bool = False, paged: bool = False,
                 block_size: int = 32, n_blocks: Optional[int] = None,
                 paged_kernel: bool = False, overcommit: float = 1.0,
                 spec_decode: bool = False, draft_planes: int = 2,
                 gamma: int = 4, precision_tiers: Optional[Dict[str, int]] = None,
                 degrade: bool = False, degrade_queue_depth: int = 2,
                 degrade_hysteresis: int = 4,
                 obs: Optional[Observability] = None):
        self.cfg = cfg
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        self.mesh = mesh
        # Observability bundle (metrics registry + flight recorder).  The
        # default is a FRESH bundle per engine so engines never share
        # telemetry; launch.serve passes one wired to the process-global
        # registry so its scrape endpoint sees this engine's metrics.
        self.obs = obs if obs is not None else Observability()
        # Model-parallel packed serving: annotate PackedWeights with their
        # mesh axes BEFORE placement, and trace every program under
        # packed_shard_mesh so the bitserial matmul runs shard_map'd on
        # per-shard packed bytes (see module docstring).
        from ..core.packing import packed_leaves

        has_packed = bool(packed_leaves(params))
        self._packed_mesh = mesh if has_packed else None
        if mesh is not None:
            from ..dist.elastic import reshard_tree

            if has_packed:
                params = dist_sharding.annotate_packed_specs(params, mesh)
            params = reshard_tree(params, mesh)
        self.params = params
        self._prefill_cache: Dict[int, Callable] = {}

        def _decode_fn(p, cache, tok, pos):
            with packed_shard_mesh(self._packed_mesh):
                return transformer.decode_step(p, cache, tok, pos, cfg)

        self._decode = jax.jit(_decode_fn)
        self.scheduler = None
        if (paged or paged_kernel) and not continuous:
            raise ValueError("paged=True requires continuous=True (the block "
                             "pool lives in the slot-pool scheduler)")
        if spec_decode and not continuous:
            raise ValueError("spec_decode=True requires continuous=True (the "
                             "draft/verify rounds live in the slot-pool "
                             "scheduler)")
        if paged_kernel and not paged:
            raise ValueError("paged_kernel=True requires paged=True — the "
                             "kernel walks the block table a dense cache "
                             "does not have")
        if continuous:
            from .scheduler import ContinuousScheduler, SchedulerPolicy

            if policy is None:
                policy = SchedulerPolicy(n_slots=n_slots,
                                         chunked_prefill=chunked_prefill or paged,
                                         paged=paged, block_size=block_size,
                                         n_blocks=n_blocks,
                                         paged_kernel=paged_kernel,
                                         overcommit=overcommit,
                                         spec_decode=spec_decode,
                                         draft_planes=draft_planes,
                                         gamma=gamma,
                                         precision_tiers=precision_tiers,
                                         degrade=degrade,
                                         degrade_queue_depth=degrade_queue_depth,
                                         degrade_hysteresis=degrade_hysteresis)
            else:
                if chunked_prefill and not policy.chunked_prefill:
                    policy = dataclasses.replace(policy, chunked_prefill=True)
                if paged and not policy.paged:
                    # paged implies chunked prefill (policy validates)
                    policy = dataclasses.replace(
                        policy, paged=True, chunked_prefill=True,
                        block_size=block_size, n_blocks=n_blocks,
                    )
                if paged_kernel and not policy.paged_kernel:
                    # requires paged (policy validates)
                    policy = dataclasses.replace(policy, paged_kernel=True)
                if overcommit != 1.0 and policy.overcommit == 1.0:
                    # requires paged (policy validates)
                    policy = dataclasses.replace(policy, overcommit=overcommit)
                if spec_decode and not policy.spec_decode:
                    # requires paged (policy validates)
                    policy = dataclasses.replace(
                        policy, spec_decode=True, draft_planes=draft_planes,
                        gamma=gamma)
                if precision_tiers is not None and policy.precision_tiers is None:
                    # requires chunked prefill (policy validates)
                    policy = dataclasses.replace(
                        policy, precision_tiers=precision_tiers)
                if degrade and not policy.degrade:
                    policy = dataclasses.replace(
                        policy, degrade=True,
                        degrade_queue_depth=degrade_queue_depth,
                        degrade_hysteresis=degrade_hysteresis)
            self.scheduler = ContinuousScheduler(self, policy)

    # -- sharding ---------------------------------------------------------
    def _prefill_fn(self, batch: int):
        """Jitted prefill for one batch size.  With a mesh, the cache's
        OUTPUT sharding is constrained to the dist rules, so XLA emits it
        directly in the serving layout (no post-hoc reshard copy); the
        decode loop then just propagates it."""
        fn = self._prefill_cache.get(batch)
        if fn is None:
            cache_dtype = jnp.dtype(self.cfg.kv_cache_dtype)
            out_sh = None
            if self.mesh is not None:
                cache_sds = jax.eval_shape(
                    lambda: transformer.init_cache(self.cfg, batch, self.max_len,
                                                   cache_dtype)
                )
                out_sh = (
                    None,
                    dist_sharding.tree_shardings(
                        self.mesh, dist_sharding.cache_tree_specs(cache_sds, self.mesh)
                    ),
                )
            def _prefill(p, b):
                with packed_shard_mesh(self._packed_mesh):
                    return transformer.prefill(p, b, self.cfg, self.max_len,
                                               cache_dtype=cache_dtype)

            fn = jax.jit(_prefill, out_shardings=out_sh)
            self._prefill_cache[batch] = fn
        return fn

    def _place_batch(self, arr: jax.Array) -> jax.Array:
        if self.mesh is None:
            return arr
        return jax.device_put(arr, dist_sharding.batch_shardings(self.mesh, arr))

    # -- sampling ---------------------------------------------------------
    def _sample(self, logits: jax.Array, temperatures: jax.Array, any_hot: bool) -> jax.Array:
        """Per-request sampling: row i uses temperatures[i]; 0 => greedy."""
        logits = logits[:, : self.cfg.vocab_size]  # mask padded vocab rows
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if not any_hot:
            return greedy
        self.key, sub = jax.random.split(self.key)
        safe_t = jnp.where(temperatures > 0, temperatures, 1.0)[:, None]
        sampled = jax.random.categorical(sub, logits / safe_t, axis=-1).astype(jnp.int32)
        return jnp.where(temperatures > 0, sampled, greedy)

    # -- batching ---------------------------------------------------------
    @staticmethod
    def _buckets(requests: List[Request]) -> Dict[int, List[Request]]:
        out: Dict[int, List[Request]] = {}
        for r in requests:
            out.setdefault(len(r.tokens), []).append(r)
        return out

    def generate(self, requests: List[Request],
                 arrival_steps: Optional[Sequence[int]] = None) -> List[Result]:
        """Serve a request set.  Continuous engines route through the
        slot-pool scheduler (``arrival_steps`` simulates staggered
        arrivals on the scheduler's step clock); bucketed engines batch
        by prompt length and ignore arrivals (offline semantics)."""
        if self.scheduler is not None:
            return self.scheduler.run(requests, arrival_steps)
        rec = self.obs.recorder
        for r in requests:
            rec.begin(r.uid)
        try:
            results = []
            for plen, bucket in self._buckets(requests).items():
                results.extend(self._run_bucket(plen, bucket))
            return results
        finally:
            # A failed bucket must not leak the remaining spans.
            for r in requests:
                if r.uid in rec.active:
                    rec.finish(r.uid, obs_trace.ABANDONED)

    def stream(self, requests: List[Request],
               arrival_steps: Optional[Sequence[int]] = None):
        """Streaming completion: yield each Result as its lane finishes
        (continuous mode only)."""
        if self.scheduler is None:
            raise ValueError("stream() requires ServeEngine(continuous=True)")
        return self.scheduler.stream(requests, arrival_steps)

    def _run_bucket(self, plen: int, bucket: List[Request]) -> List[Result]:
        B = len(bucket)
        rec = self.obs.recorder
        h_ttft = self.obs.registry.histogram(
            "serve_ttft_ms",
            "time to first token (admitted -> first_token span, ms)")
        c_req = self.obs.registry.counter(
            "serve_requests_total", "requests retired, by terminal outcome",
            labels=("outcome",))
        prompts = self._place_batch(jnp.asarray(np.stack([r.tokens for r in bucket])))
        temps = jnp.asarray([r.temperature for r in bucket], jnp.float32)
        any_hot = any(r.temperature > 0 for r in bucket)
        max_new = max(r.max_new for r in bucket)
        # The bucket's prefill dispatch is every member's admission.
        t0 = obs_trace.now()
        for r in bucket:
            rec.event(r.uid, obs_trace.ADMITTED, ts=t0, batch=B)
        logits, cache = self._prefill_fn(B)(self.params, {"tokens": prompts})
        tok = self._sample(logits, temps, any_hot)
        jax.block_until_ready(tok)
        # TTFT = admitted -> first SAMPLED token, matching the continuous
        # scheduler (the pre-obs bucketed path stopped its clock before
        # sampling — the drift tests/test_obs.py now pins away).
        t_first = obs_trace.now()
        for r in bucket:
            rec.event(r.uid, obs_trace.FIRST_TOKEN, ts=t_first)
        out_toks = [tok]
        for t in range(max_new - 1):
            logits, cache = self._decode(self.params, cache, tok[:, None], jnp.int32(plen + t))
            tok = self._sample(logits, temps, any_hot)
            out_toks.append(tok)
        gen = np.asarray(jnp.stack(out_toks, axis=1))
        results = []
        for i, r in enumerate(bucket):
            tr = rec.finish(r.uid, obs_trace.FINISHED, n_tokens=r.max_new)
            c_req.labels(outcome="finished").inc()
            h_ttft.observe(tr.ttft_ms())
            results.append(Result(r.uid, gen[i, : r.max_new], tr.ttft_ms()))
        return results


def dequantize_packed_params(template, packed: Dict[str, "object"], floats: Dict[str, jax.Array]):
    """Materialise a float param tree from a BSQ packed export (ref path —
    the Pallas path dequantises inside the matmul instead)."""
    from ..core.bsq import merge_params
    from ..core.packing import unpack_to_float

    flat = {}
    for name, pw in packed.items():
        flat[name] = unpack_to_float(pw)
    return merge_params(template, flat, floats)
