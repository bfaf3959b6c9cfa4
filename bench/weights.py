"""Seeded random weights, made by the benchmark and not by the program.

Every packed matrix is drawn directly in its served form: uniform random
bit-plane and sign bytes (so each magnitude code is uniform on
``0 .. 2^n - 1`` and each sign a fair coin) and one scale per layer.
The embedding and the norm gains are Gaussian.  Each layer's leaves
come from a key folded from the seed, the leaf's name and the layer
index, so the reference regenerates any single layer on its own and
never reads an array the program holds.

What depends on the architecture is the reference module's
(``bench.check.reference``): which roles a layer of each kind holds,
each packed matrix's shape and weight scale, and how each other float
leaf is drawn.  The final norm's gain is drawn around zero so that tied
logits do not simply echo the input token; like the scales, that only
fixes what random weights look like, and the kernels do the same work
for any bytes.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

def seed_words(seed: int):
    """A seed of up to 64 bits as two uint32 words, passed to the jitted
    generators as data so that one compiled program serves every seed."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not a whole number in [0, 2**64)")
    return jnp.asarray([seed % 2**32, seed >> 32], jnp.uint32)


def _key(seed, name: str, layer):
    k = jax.random.fold_in(jax.random.key(seed[0]), seed[1])
    k = jax.random.fold_in(k, zlib.crc32(name.encode()))
    return jax.random.fold_in(k, layer + 1)


def _bytes(key, shape):
    """Uniform random uint8 of ``shape`` (last axis divisible by 4),
    drawn as uint32 words so no wider temporary is made."""
    words = jax.random.bits(key, shape[:-1] + (shape[-1] // 4,), jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(shape)


def code_scale(std: float, n_bits: int) -> float:
    """Scale ``s`` such that ``sign * q * s / (2^n - 1)``, with ``q``
    uniform on ``0 .. 2^n - 1``, has standard deviation ``std``."""
    lv = 2**n_bits - 1
    return std / math.sqrt((2 * lv + 1) / (6 * lv))


def packed_leaf(seed, name: str, layer: int, k: int, n: int, n_bits: int, std: float):
    """(planes (n_bits, k/8, n), sign (k/8, n), scale ()) of one matrix
    whose dequantised weight has standard deviation ``std``."""
    key = _key(seed, name, layer)
    kp, ks = jax.random.split(key)
    planes = _bytes(kp, (n_bits, k // 8, n))
    sign = _bytes(ks, (k // 8, n))
    scale = jnp.float32(code_scale(std, n_bits))
    return planes, sign, scale


def norm_gain(seed, name: str, layer: int, d: int):
    """Gain ``g`` of an RMSNorm (the published ``weight``): 1 + N(0, 0.1)
    in the blocks; N(0, 1) for the final norm (see the module doc)."""
    z = jax.random.normal(_key(seed, name, layer), (d,), jnp.float32)
    return z if name == "final_norm" else 1.0 + 0.1 * z


def embedding(seed, vocab_rows: int, d: int):
    z = jax.random.normal(_key(seed, "embed", -1), (vocab_rows, d), jnp.float32)
    return z / math.sqrt(d)


def _path_names(path):
    return [str(getattr(q, "key", getattr(q, "name", getattr(q, "idx", q)))) for q in path]


def program_params(seed: int, template, ref, model: dict, n_bits: int, layers_per_block: int,
                   packed_type):
    """The program's parameter tree, filled from ``seed`` in one jitted call.

    ``template`` is the program's own tree of shapes (``jax.eval_shape``
    of its packed init); only its structure is used.  Layer ``l`` of the
    depth sits at ``blocks/p{l % P}`` slice ``l // P`` (P =
    ``layers_per_block``).  The reference module ``ref`` says which roles
    a layer of each kind holds (``ref.dims_of(model)``).  A packed leaf
    of a role is drawn at the role's ``ref.weight_std``, a leaf
    ``<role>/scale`` is the gain of an RMSNorm, and any other leaf is
    drawn by ``ref.FLOAT_LEAVES[role]``.  Norm leaves hold ``gain - 1``:
    the program's RMSNorm multiplies by ``1 + scale``.  Any leaf the
    reference does not name raises, so a change of the program's tree
    cannot pass unseen."""
    is_pw = lambda x: isinstance(x, packed_type)
    flat, treedef = jax.tree_util.tree_flatten_with_path(template, is_leaf=is_pw)
    dims = ref.dims_of(model)
    L = dims["n_layers"]

    def roles_at(pos: int, nb: int):
        kinds = {dims["kinds"][l] for l in range(pos, nb * layers_per_block, layers_per_block)}
        if len(kinds) != 1:
            raise ValueError(f"layers at pattern position {pos} are of kinds {sorted(kinds)} "
                             "in the reference: the program's pattern does not repeat them")
        return dims["roles"][kinds.pop()]

    def build(seed):
        leaves = []
        for path, leaf in flat:
            names = _path_names(path)
            if names[0] == "blocks":
                pos = int(names[1][1:])
                nb = (leaf.planes if is_pw(leaf) else leaf).shape[0]
                layers = jnp.arange(nb) * layers_per_block + pos
                roles, role = roles_at(pos, nb), names[-1]
                if is_pw(leaf) and role in roles:
                    k, n = dims[role]
                    std = ref.weight_std(role, k, L)
                    planes, sign, scale = jax.vmap(
                        lambda l, r=role, k=k, n=n, std=std:
                        packed_leaf(seed, r, l, k, n, n_bits, std))(layers)
                    leaves.append(packed_type(planes=planes, sign=sign,
                                              scale=scale.reshape(nb, 1, 1),
                                              n_bits=leaf.n_bits, k=leaf.k))
                    continue
                if role == "scale" and names[-2] in roles:
                    g = jax.vmap(lambda l, r=names[-2], d=leaf.shape[-1]:
                                 norm_gain(seed, r, l, d))(layers)
                    leaves.append(g - 1.0)
                    continue
                if not is_pw(leaf) and role in roles and role in ref.FLOAT_LEAVES:
                    x = jax.vmap(lambda l, r=role, shape=leaf.shape[1:]:
                                 ref.FLOAT_LEAVES[r](_key(seed, r, l), shape))(layers)
                    leaves.append(x.astype(leaf.dtype))
                    continue
            elif names == ["embed"]:
                leaves.append(embedding(seed, *leaf.shape))
                continue
            elif names == ["final_norm", "scale"]:
                leaves.append(norm_gain(seed, "final_norm", -1, dims["d_model"]) - 1.0)
                continue
            elif names == ["lm_head"] and is_pw(leaf):
                k, n = dims["lm_head"]
                planes, sign, scale = packed_leaf(seed, "lm_head", -1, k, n, n_bits,
                                                  ref.weight_std("lm_head", k, L))
                leaves.append(packed_type(planes=planes, sign=sign, scale=scale,
                                          n_bits=leaf.n_bits, k=leaf.k))
                continue
            raise ValueError(f"no seeded weight for program leaf {'/'.join(names)}")
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_words(seed))
