"""The correctness check: served tokens against the plain reference.

After the window, a sample of the requests that were served tokens,
drawn from the seed and always holding the one served the most, is run
through the float32 reference (``reference/<module>.py``) over its
prompt and its served tokens.  At each served position the gap is how
far the served token's reference logit lies below the reference's best.
Greedy serving in bfloat16 puts the widest gap (``gap_max``) at the size
of its rounding; a wrong token, a wrong cache entry or a lower precision
puts it far above.  The mean gap (``gap_mean``) is read beside it.  A
run is held to the numbers its cell file gives a limit.
"""

from __future__ import annotations

import types
from typing import List, Sequence

import numpy as np

SAMPLE_TOKENS = 300      # served tokens the sample holds at least
SAMPLE_MIN = 4           # requests at least, where the run served as many
SAMPLE_MAX = 16          # requests at most
OTHERS_CAP = 256         # served tokens compared of each but the longest


def sample(records: Sequence, seed: int) -> List:
    """The request served the most tokens, then others in an order drawn
    from the seed until the sample holds ``SAMPLE_MIN`` requests and
    ``SAMPLE_TOKENS`` served tokens."""
    done = [r for r in records if r.status != "failed" and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.tokens), r.rid))
    picked, rest = [done[0]], done[1:]
    rng = np.random.default_rng(seed)
    for i in rng.permutation(len(rest)):
        if len(picked) >= SAMPLE_MAX or (
                len(picked) >= SAMPLE_MIN and
                sum(len(r.tokens) for r in picked) >= SAMPLE_TOKENS):
            break
        picked.append(rest[i])
    return picked


def sequences(picked: Sequence) -> List:
    """``(prompt + served tokens, index of the first served token)``; all
    of the longest request's tokens, the first ``OTHERS_CAP`` of the
    others'."""
    return [(np.concatenate([r.prompt, np.asarray(
        r.tokens if i == 0 else r.tokens[:OTHERS_CAP], np.int32)]),
        len(r.prompt)) for i, r in enumerate(picked)]


def numbers(ref: types.ModuleType, config: dict, seed: int, seqs) -> dict:
    """The widest gap of a served token below the reference's best, and
    the mean gap over every served token compared; ``ref`` is the
    configuration's reference module.  With nothing served, both are
    infinite: a run that served nothing is not correct."""
    if not seqs:
        return {"gap_max": float("inf"), "gap_mean": float("inf")}
    g = np.concatenate(ref.gaps(config, seed, seqs))
    return {"gap_max": float(g.max()), "gap_mean": float(g.mean())}


def readings(ref: types.ModuleType, config: dict, seed: int, seqs) -> dict:
    """The program's gaps and the control's on the same positions, by
    their widest and their mean (``tools/readings.py``, the tests)."""
    served, ctl = ref.gaps(config, seed, seqs, control=True)
    s, c = np.concatenate(served), np.concatenate(ctl)
    return {"gap_max": float(s.max()), "gap_mean": float(s.mean()),
            "control_gap_max": float(c.max()),
            "control_gap_mean": float(c.mean())}
