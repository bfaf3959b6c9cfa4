"""Partition rules: name/shape-driven PartitionSpecs for every tensor.

This module is the ONLY place in the system that decides how a tensor is
laid out over the ``("data", "model")`` (optionally ``("pod", "data",
"model")``) mesh.  Rules are keyed on the "/"-joined pytree path and the
shape — never on concrete values — so the same rules drive real arrays,
ShapeDtypeStructs (dry-run lowering) and checkpoint restore targets.

Rule summary (2x4 mesh shown as data=2, model=4):

==========================================  =================================
tensor                                      spec
==========================================  =================================
col-parallel matmul  ``wq`` (L, in, out)    ``P(None, "data", "model")``
row-parallel ``wo``/``w_down`` (L, in, out) ``P(None, "model", "data")``
BSQ planes ``.../wq/wp`` (nb, L, in, out)   base rule + leading ``None``
packed ``.../wq/planes`` (L, nb, K/8, out)  base rule + ``None`` bit axis
packed ``.../wq/sign`` (L, K/8, out)        base rule (K/8 on the K axis)
packed scale row ``.../wq/scale`` (.., 1, G) group axis follows base out axis
embedding ``embed`` (V, d)                  ``P("model", "data")``
stacked MoE experts (L, E, in, out)         experts -> ``"model"``
norm scales / biases / BSQ scales / masks   replicated
KV cache (B, S, KV, hd)                     ``P("data", None, "model", None)``
paged KV block pool (Nb, KV, bs, hd)        block axis -> ``"data"`` (as slots)
block table (n_slots, blocks_per_lane)      lanes -> data axes when they
                                            co-shard with pool blocks,
                                            else replicated
pool control vectors (pos, temps, ...)      replicated
KV cache, KV-heads % model != 0             seq -> ``"model"`` instead
KV cache, batch 1 (long context)            seq -> ``("data", "model")``
any other dim not divisible by its axis     that dim replicated
==========================================  =================================
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.packing import PACKABLE_SUFFIXES

PyTree = Any

# Pytree wrapper segments that may prefix a model-param path inside a
# train-state tree (state dicts, optimizer moments, BSQ containers).
_WRAPPERS = frozenset(
    {"trainable", "opt", "masks", "reps", "float", "params", "mu", "nu", "residual"}
)

# Leaf names whose matmul convention is row-parallel (input dim is the
# sharded contraction axis): attention output and down projections.
_ROW_PARALLEL = frozenset({"wo", "out_proj", "w_out", "w_down"})

# Stacked-expert MoE weights (leading expert axis under /moe/).
_MOE_EXPERT = frozenset({"w_gate", "w_up", "w_down"})

# Matmul leaf names that may be replaced by a PackedWeight (used to tell
# a packed scale row ".../wq/scale" apart from a norm gain
# ".../norm1/scale").
_PACKED_PARENTS = frozenset(PACKABLE_SUFFIXES)

# Name fragments that force replication: norms, biases, per-group scales,
# recurrence scalars, depthwise convs — all tiny and/or value-coupled.
_REPLICATED_FRAGMENTS = (
    "norm", "scale", "bias", "lambda", "a_log", "d_skip", "conv",
    "step", "count", "rope", "pact", "pos_emb",
)


def replicated() -> P:
    """The fully-replicated spec (scalars, tiny tensors)."""
    return P()


# ---------------------------------------------------------------------------
# Mesh helpers
# ---------------------------------------------------------------------------


def _axis_size(mesh, axis: str) -> int:
    return int(mesh.shape[axis]) if axis in mesh.shape else 0


def mesh_labels(mesh) -> dict:
    """Metric labels identifying this process's mesh placement.

    ``{"mesh": "dp2xtp4", "process": "0"}`` for a 2x4 mesh (or
    ``{"mesh": "none", "process": "0"}`` single-device) — attached to
    the serve allocator's per-shard metric families so a scraped
    exposition says *which* topology produced the numbers."""
    if mesh is None:
        return {"mesh": "none", "process": str(jax.process_index())}
    shape = "x".join(f"{ax}{n}" for ax, n in mesh.shape.items())
    return {"mesh": shape or "none", "process": str(jax.process_index())}


def _fits(mesh, axis: str, dim: int) -> bool:
    n = _axis_size(mesh, axis)
    return n > 0 and dim % n == 0


def dp_axes(mesh, dim: int):
    """Data-parallel assignment for a batch-like dim: ("pod", "data") when
    both exist and divide, else "data", else None (replicated)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    for cand in (axes, axes[-1:]):
        if not cand:
            continue
        total = 1
        for a in cand:
            total *= _axis_size(mesh, a)
        if total > 0 and dim % total == 0:
            return cand[0] if len(cand) == 1 else cand
    return None


def _path_name(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p).strip("."))
    return "/".join(parts)


def _canonical(name: str) -> Tuple[str, ...]:
    """Strip state-tree wrapper segments so ``opt/mu/reps/blocks/...`` and
    ``blocks/...`` resolve to the same rule."""
    segs = [s for s in name.split("/") if s]
    while segs and segs[0] in _WRAPPERS:
        segs.pop(0)
    return tuple(segs)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------


def param_spec(name: str, shape: Tuple[int, ...], mesh) -> P:
    """PartitionSpec for one (possibly stacked) parameter tensor.

    ``name`` is the "/"-joined pytree path; wrapper segments from train
    state (``trainable/reps/...``, ``opt/mu/...``, ``masks/...``) are
    stripped, so the same rules cover params, optimizer moments and BSQ
    bit-plane state.
    """
    segs = _canonical(name)
    ndim = len(shape)
    if not segs or ndim == 0:
        return replicated()
    leaf = segs[-1].lower()

    # BSQ bit-plane tensors (wp / wn) carry a leading plane axis and
    # inherit the base weight's layout (the planes of one weight must live
    # with that weight for reconstruct/regularise to stay local).
    if leaf in ("wp", "wn") and ndim >= 1:
        base = "/".join(segs[:-1])
        return P(None, *param_spec(base, shape[1:], mesh))

    # Packed serving weights follow the BASE weight's layout: sign
    # (..., K/8, N) takes the base rule directly (byte-packed K rows and
    # the output dim land on the base's in/out axes), planes
    # (..., n_bits, K/8, N) add a replicated bit axis in front of the
    # trailing two.  The Pallas bitserial kernel is still a custom call
    # GSPMD cannot partition — the serve path wraps it in shard_map
    # (kernels.ops.bitserial_matmul_sharded) so each shard runs the
    # kernel on its LOCAL packed bytes and a psum stitches the
    # contraction; per-shard packing comes from
    # core.bsq.export_packed_sharded.
    if leaf in ("planes", "sign") and ndim >= 2:
        base = "/".join(segs[:-1])
        if leaf == "planes":
            if ndim < 3:
                return replicated()
            bspec = tuple(param_spec(base, shape[:-3] + shape[-2:], mesh))
            return P(*bspec[:-2], None, *bspec[-2:])
        return param_spec(base, shape, mesh)

    # Per-group packed scale rows (..., 1, G) live on the shard that owns
    # their output columns: recurse into the BASE weight's rule with the
    # row's own shape — the 1-sized K slot never fits a mesh axis, and
    # the G slot shards onto the base's out axis iff it divides — so the
    # scale can never drift from the planes/sign layout (a tiny row, but
    # a shard_map'd epilogue needs its local groups resident).  Everything
    # else named "scale" (norm gains, BSQ training scales with trivial
    # rows) falls through to the replicated rule below.
    if (
        leaf == "scale"
        and len(segs) >= 2
        and segs[-2].lower() in _PACKED_PARENTS
        and ndim >= 2
        and shape[-2] == 1
        and shape[-1] > 1
    ):
        return param_spec("/".join(segs[:-1]), shape, mesh)

    if ndim < 2 or any(f in leaf for f in _REPLICATED_FRAGMENTS):
        return replicated()

    # Embedding table: vocab -> model (the softmax/logit contraction axis),
    # d_model -> data.  (cross_entropy keeps the vocab-sharded layout.)
    if leaf == "embed" and ndim == 2:
        return P(
            "model" if _fits(mesh, "model", shape[0]) else None,
            "data" if _fits(mesh, "data", shape[1]) else None,
        )

    # Stacked MoE expert weights (L?, E, d_in, d_out): experts -> model
    # (expert parallelism; the dispatch einsum induces the all-to-all).
    # The freed mesh axis goes to the dim "model" would otherwise take.
    if leaf in _MOE_EXPERT and "moe" in segs and "shared" not in segs and ndim >= 3:
        spec = [None] * ndim
        e_ax = ndim - 3
        if _fits(mesh, "model", shape[e_ax]):
            spec[e_ax] = "model"
        d_ax = ndim - 2 if leaf == "w_down" else ndim - 1  # row- vs col-parallel
        if _fits(mesh, "data", shape[d_ax]):
            spec[d_ax] = "data"
        return P(*spec)

    # Dense matmul weights (..., d_in, d_out); leading axes (scan-stacked
    # layers, tail indices) stay replicated.
    spec = [None] * ndim
    if leaf in _ROW_PARALLEL:
        in_ax, out_ax = ("model", "data")
    else:  # col-parallel: wq/wk/wv, w_gate/w_up, in_proj, lm_head, ...
        in_ax, out_ax = ("data", "model")
    if _fits(mesh, in_ax, shape[-2]):
        spec[-2] = in_ax
    if _fits(mesh, out_ax, shape[-1]):
        spec[-1] = out_ax
    return P(*spec)


def tree_param_specs(tree: PyTree, mesh) -> PyTree:
    """Map :func:`param_spec` over a whole pytree (params or train state).

    Works on concrete arrays and ShapeDtypeStructs alike; PackedWeight
    dataclasses are descended into (their planes/sign/scale fields get
    their own rules).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = [param_spec(_path_name(path), tuple(leaf.shape), mesh) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def annotate_packed_specs(params: PyTree, mesh) -> PyTree:
    """Stamp every PackedWeight in ``params`` with its ``kn_spec``.

    ``kn_spec`` is the (K-axis, N-axis) mesh-axis pair of the weight's
    trailing two logical dims — the static annotation
    ``kernels.ops.bitserial_matmul_sharded`` needs to shard_map the
    Pallas kernel over per-shard packed bytes (the byte tensors
    themselves are placed by :func:`tree_param_specs`; this records
    *which* axes they landed on, since a traced value's sharding cannot
    be inspected at trace time).  Derived from the ``sign`` leaf's rule
    so annotation and placement cannot drift.
    """
    import dataclasses

    from ..core.packing import PackedWeight

    def is_pw(x):
        return isinstance(x, PackedWeight)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params, is_leaf=is_pw)
    out = []
    for path, leaf in flat:
        if is_pw(leaf):
            spec = tuple(
                param_spec(_path_name(path) + "/sign", tuple(leaf.sign.shape), mesh)
            )
            kn = (spec[-2], spec[-1]) if len(spec) >= 2 else (None, None)
            out.append(dataclasses.replace(leaf, kn_spec=kn))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Cache rules
# ---------------------------------------------------------------------------


def cache_spec(name: str, shape: Tuple[int, ...], mesh) -> P:
    """PartitionSpec for one decode-cache tensor (no leading stack axis).

    KV tensors are (B, S, KV, hd): batch -> data, kv-heads -> model, with
    two fallbacks — a kv-head count the model axis doesn't divide (MQA's
    1, or small GQA counts) moves "model" to the sequence axis (decode
    writes stay shard-local: each token's update lands on one seq shard;
    the attention read becomes a psum, same pattern as row-parallel), and
    a batch of exactly 1 (long context) additionally spreads the sequence
    over the data axes.  Any other indivisible dim is replicated.
    Recurrent state/conv tensors shard batch only (their channel math is
    value-coupled across features).
    """
    leaf = name.split("/")[-1].lower()
    ndim = len(shape)
    if leaf in ("k", "v", "kv") and ndim == 4:
        B, S, KV, _ = shape
        spec: list = [None] * 4
        spec[0] = dp_axes(mesh, B)
        if KV > 1 and _fits(mesh, "model", KV):
            spec[2] = "model"
        elif _fits(mesh, "model", S):
            spec[1] = "model"
        if B == 1:
            # batch-1 long context: the sequence is the only big axis left.
            # (Indivisible B > 1 keeps the batch axis replicated instead —
            # the rule-table default — so small uneven buckets don't pay
            # per-token scatter traffic on a sequence-sharded cache.)
            dm = _axis_size(mesh, "data") * max(_axis_size(mesh, "model"), 1)
            if spec[1] == "model" and _axis_size(mesh, "data") > 0 and S % dm == 0:
                spec[1] = ("data", "model")
            elif spec[1] is None:
                spec[1] = dp_axes(mesh, S)
        return P(*spec)
    # Recurrent caches (ssm/rglru state, conv tails): batch-sharded only.
    spec = [None] * ndim
    if ndim >= 1:
        spec[0] = dp_axes(mesh, shape[0])
    return P(*spec)


def slot_pool_specs(pool_state: PyTree, mesh) -> PyTree:
    """Specs for a continuous-batching slot pool (serve/slots.py).

    The pool's decode cache (batch axis = n_slots) shards under the cache
    rules — slots spread over the data axes, KV heads over model.  The
    per-slot control vectors (``pos``, ``temps``, any other (n_slots,)
    leaf outside "cache") stay replicated: they are tiny, participate in
    every lane's masking, and the admission scatter updates single
    elements — sharding them would turn each admission into a
    one-element collective.
    """
    return {
        k: cache_tree_specs(v, mesh) if k == "cache" else jax.tree.map(lambda _: replicated(), v)
        for k, v in pool_state.items()
    }


def paged_block_spec(shape: Tuple[int, ...], mesh) -> P:
    """Spec for one paged KV pool leaf ``(n_blocks, KV, block_size, hd)``.

    The block axis takes the slot axis's role and spreads over the data
    axes; KV heads go to model when divisible.  The intra-block row axis
    is NEVER sharded: a block is the unit of table indirection — every
    gather/scatter addresses whole blocks through traced ids, and
    splitting a block's rows across devices would turn each of those
    accesses into a cross-device reshuffle (XLA falls back to full
    rematerialisation of the pool per step).
    """
    Nb, KV, _bs, _hd = shape
    spec: list = [None] * 4
    spec[0] = dp_axes(mesh, Nb)
    if KV > 1 and _fits(mesh, "model", KV):
        spec[1] = "model"
    return P(*spec)


def block_table_spec(n_slots: int, n_blocks: int, mesh) -> P:
    """Spec for the per-lane block table ``(n_slots, blocks_per_lane)``.

    The lane axis shards over the data axes when — and only when — the
    pool's block axis shards over the *same* axes: shard s's lanes must
    own exactly shard s's blocks, so the shard-local decode path
    (``models.attention._paged_attend_sharded`` +
    ``BlockAllocator(n_shards=D)``) can translate global block ids with
    a subtraction and never touch another shard's pool slice.  When
    either count doesn't divide (or they land on different axis tuples)
    the table replicates, and the pool gathers run under GSPMD as
    before.  Entries within a lane's row never shard — a gather consumes
    the whole row.
    """
    ax = dp_axes(mesh, n_slots)
    if ax is None or dp_axes(mesh, n_blocks) != ax:
        return replicated()
    return P(ax, None)


def table_shards(mesh, n_slots: int, n_blocks: int) -> int:
    """How many shards :func:`block_table_spec` splits the lane axis into
    (1 = replicated).  The serve-side allocator mirrors this as its
    per-shard free-list count."""
    if mesh is None:
        return 1
    spec = block_table_spec(n_slots, n_blocks, mesh)
    if len(spec) == 0 or spec[0] is None:
        return 1
    return _axis_size(mesh, spec[0])


def lane_shard(slot: int, n_slots: int, n_shards: int) -> int:
    """Which table shard lane ``slot`` belongs to: contiguous lane
    groups, matching how shard_map splits the lane axis (shard s owns
    lanes ``[ceil(s*n_slots/n_shards), ceil((s+1)*n_slots/n_shards))``).
    This is the layout contract the serve-side allocator and the
    scheduler's shard-aware admission/victim selection both lean on —
    it lives here so the mapping can never drift from
    :func:`block_table_spec`'s split."""
    return slot * n_shards // n_slots


def shard_lanes(shard: int, n_slots: int, n_shards: int) -> range:
    """Inverse of :func:`lane_shard`: the contiguous lane range shard
    ``shard`` owns.  Used by shard-aware victim selection — a lane can
    only relieve block pressure in its own shard's pool range."""
    lo = -(-shard * n_slots // n_shards)
    hi = -(-(shard + 1) * n_slots // n_shards)
    return range(lo, hi)


def block_pool_specs(pool_state: PyTree, mesh, n_blocks: int, block_size: int) -> PyTree:
    """Specs for a PAGED slot pool (serve/slots.py with ``paged=True``).

    Cache leaves whose leading dims match the block pool shape take
    :func:`paged_block_spec`; everything else in the cache (ring buffers,
    recurrent state — still per-lane) keeps the ordinary cache rules.
    The per-lane ``block_table`` shards over the data axes when lanes
    and pool blocks co-shard (:func:`block_table_spec`) so the decode
    step can run shard-local; the remaining control vectors (``pos``,
    ``temps``, ...) stay replicated: they are tiny, participate in every
    lane's masking, and admission scatters write single elements.
    """
    def cache_specs(cache):
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
        specs = []
        for path, leaf in flat:
            name = _path_name(path)
            segs = name.split("/")
            stacked = segs and segs[0] == "blocks"
            shape = tuple(leaf.shape)[1:] if stacked else tuple(leaf.shape)
            if (segs[-1].lower() in ("k", "v") and len(shape) == 4
                    and (shape[0], shape[2]) == (n_blocks, block_size)):
                s = paged_block_spec(shape, mesh)
            else:
                s = cache_spec(segs[-1], shape, mesh)
            specs.append(P(None, *s) if stacked else s)
        return jax.tree_util.tree_unflatten(treedef, specs)

    def other_specs(k, v):
        if k == "block_table":
            return jax.tree.map(
                lambda leaf: block_table_spec(leaf.shape[0], n_blocks, mesh), v
            )
        return jax.tree.map(lambda _: replicated(), v)

    return {
        k: cache_specs(v) if k == "cache" else other_specs(k, v)
        for k, v in pool_state.items()
    }


def chunk_buffer_specs(buffers: PyTree, mesh) -> PyTree:
    """Specs for chunked-prefill staging buffers (serve/scheduler.py).

    The per-dispatch control tensors — the (n_slots, C) token block, the
    per-lane ``start`` / ``n_valid`` vectors and the multi-admit slot
    vector — are tiny and consumed by every lane's masking math, so they
    replicate like the pool's ``pos``/``temps`` vectors (sharding the
    slot axis would turn each chunk dispatch into a collective).  Kept as
    an explicit rule so the layout decision lives here, not in serve/.
    """
    return jax.tree.map(lambda _: replicated(), buffers)


def cache_tree_specs(cache: PyTree, mesh) -> PyTree:
    """:func:`cache_spec` over a whole decode cache; entries under
    ``blocks`` carry a leading superblock axis (replicated)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    specs = []
    for path, leaf in flat:
        name = _path_name(path)
        segs = name.split("/")
        if segs and segs[0] == "blocks":
            specs.append(P(None, *cache_spec(segs[-1], tuple(leaf.shape)[1:], mesh)))
        else:
            specs.append(cache_spec(segs[-1], tuple(leaf.shape), mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Batch rules + NamedSharding convenience wrappers
# ---------------------------------------------------------------------------


def data_batch_spec(mesh, batch_dim: int, ndim: int) -> P:
    """Input batches: leading dim over the DP axes, rest replicated."""
    spec = [None] * ndim
    if ndim >= 1:
        spec[0] = dp_axes(mesh, batch_dim)
    return P(*spec)


def tree_shardings(mesh, spec_tree: PyTree) -> PyTree:
    """PartitionSpec tree -> NamedSharding tree (specs are leaves)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


def batch_shardings(mesh, batch_tree: PyTree) -> PyTree:
    """NamedShardings for a batch pytree (arrays or ShapeDtypeStructs)."""
    return jax.tree.map(
        lambda x: NamedSharding(mesh, data_batch_spec(mesh, x.shape[0], len(x.shape))),
        batch_tree,
    )


def scalar_sharding(mesh) -> NamedSharding:
    return NamedSharding(mesh, replicated())
