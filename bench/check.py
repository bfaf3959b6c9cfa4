"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
of the requests the window finished (the longest, then others drawn
from the seed until some hundreds of served tokens are in it) is run
through the configuration's plain reference, one sequence of prompt and
served tokens at a time.  Every served token was the program's greedy
choice; the number compared is the widest gap by which a served token's
reference logit lies below the reference's best logit at its position.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Sequence

import numpy as np

SAMPLE_TOKENS = 2048  # served tokens the sample reaches before it stops growing
SAMPLE_MAX = 16  # requests at most


def sample(finished: Dict[int, np.ndarray], seed: int) -> List[int]:
    """uids of the requests to compare: the longest first, then a seeded
    draw of the rest."""
    if not finished:
        return []
    order = sorted(finished, key=lambda u: (-len(finished[u]), u))
    picked, total = [order[0]], len(finished[order[0]])
    rest = list(np.random.default_rng(seed).permutation(order[1:]))
    while rest and total < SAMPLE_TOKENS and len(picked) < SAMPLE_MAX:
        u = int(rest.pop())
        picked.append(u)
        total += len(finished[u])
    return picked


def reference(config: dict):
    """The module ``bench/references/<reference>.py`` that the
    configuration names: the harness's one source of what depends on the
    architecture (``logits``, ``dims_of``, ``weight_std``,
    ``FLOAT_LEAVES``, ``program_differs``, ``model_flops``; see
    ``bench/references/dense.py``)."""
    return importlib.import_module(f"bench.references.{config['reference']}")


def sequence(prompt: np.ndarray, served: np.ndarray, length: int) -> np.ndarray:
    """Prompt then served tokens but the last, padded at the end to
    ``length`` (padding sits after every position read, so causal
    attention never sees it)."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    return np.pad(seq, (0, length - len(seq)))


def gaps(ref_logits: np.ndarray, prompt_len: int, tokens: Sequence[int]) -> np.ndarray:
    """Gap below the best reference logit of each token, read at the
    position that predicts it."""
    rows = ref_logits[prompt_len - 1: prompt_len - 1 + len(tokens)]
    return rows.max(axis=-1) - rows[np.arange(len(tokens)), np.asarray(tokens)]


def served_gaps(config: dict, seed: int, prompts: Dict[int, np.ndarray],
                finished: Dict[int, np.ndarray], length: int,
                control: bool = False) -> Dict[str, float]:
    """Widest gap of the served tokens of the sampled requests; with
    ``control``, also the widest gap of the token the fp8 reference puts
    first at each of the same positions."""
    ref = reference(config)
    out = {"max_logit_gap": 0.0, "tokens_compared": 0, "requests_compared": 0}
    if control:
        out["control_max_logit_gap"] = 0.0
    for uid in sample(finished, seed):
        served = finished[uid]
        seq = sequence(prompts[uid], served, length)
        plen = len(prompts[uid])
        r = np.asarray(ref.logits(seed, seq, config["model"], config["n_bits"], "f32"))
        g = gaps(r, plen, served)
        out["max_logit_gap"] = max(out["max_logit_gap"], float(g.max()))
        out["tokens_compared"] += len(served)
        out["requests_compared"] += 1
        if control:
            c = np.asarray(ref.logits(seed, seq, config["model"], config["n_bits"], "fp8"))
            rows = slice(plen - 1, plen - 1 + len(served))
            top = c[rows].argmax(axis=-1)
            out["control_max_logit_gap"] = max(out["control_max_logit_gap"],
                                               float(gaps(r, plen, top).max()))
    return out
