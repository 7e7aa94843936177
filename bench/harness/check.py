"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests it finished (drawn from the seed, the longest always in it)
is run through the configuration's plain reference: one forward pass over
each prompt followed by the tokens the program served.  At each served
token the gap ``max(reference logits) - reference logit of the served
token`` is 0 where the program chose the reference's best token and small
where rounding made it choose a near tie.  The widest gap and the mean
gap over the sample are each compared with the configuration's limit.

The control (``control=True``, for calibration and the tests, never in the
benchmark's own runs) puts the fp8 reference in the program's place: at
each position of the same sequences, the token that the fp8 reference puts
first stands in for the served token, and its gaps are the readings that
are compared.  fp8 is the precision step below the served bfloat16, so a
sound limit makes a control run come out not correct.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Sequence

import numpy as np

from . import traffic as T


@dataclass
class Served:
    prompt: np.ndarray       # (prompt_len,) int32
    tokens: np.ndarray       # (decode_tokens,) int32, as served


def sample(served: Sequence[Served], n: int, seed: int) -> List[Served]:
    """``n`` of ``served`` drawn from the seed; the longest (prompt plus
    served tokens; the first such on ties) is always one of them."""
    if not served:
        return []
    sizes = [len(s.prompt) + len(s.tokens) for s in served]
    longest = int(np.argmax(sizes))
    rest = [i for i in range(len(served)) if i != longest]
    g = T.rng(seed, T.STREAM_CHECK)
    pick = g.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [served[longest]] + [served[rest[int(i)]] for i in sorted(pick)]


def gaps(ref_logits: Sequence[np.ndarray],
         chosen: Sequence[np.ndarray]) -> np.ndarray:
    """``max(row) - row[chosen]`` for every row of every sequence."""
    return np.concatenate([lg.max(axis=-1) - lg[np.arange(len(tok)), tok]
                           for lg, tok in zip(ref_logits, chosen)])


def limits(config: Dict[str, Any], traffic_name: str) -> Dict[str, float]:
    """The configuration's limit on each number it compares (the widest
    and the mean gap), for this traffic where it names one."""
    out = {}
    for name, lim in config["check"].items():
        if isinstance(lim, dict):
            lim = lim.get(traffic_name, lim["default"])
        out[name] = float(lim)
    return out


def _readings(g: np.ndarray) -> Dict[str, float]:
    return {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean())}


def compare(ref_module: Any, arch: Dict[str, Any], key_int: int,
            chosen: Sequence[Served], control: bool = False
            ) -> Dict[str, Any]:
    """The readings of one sample: the widest and mean gap of the served
    tokens or, with ``control``, of the fp8 control's tokens in their
    place, with the served tokens' own readings under ``"served"``."""
    seqs = [np.concatenate([s.prompt, s.tokens[:-1]]).astype(np.int32)
            for s in chosen]
    plens = [len(s.prompt) for s in chosen]
    ref, ctl = ref_module.reference_logits(arch, key_int, seqs, plens,
                                           control=control)
    out: Dict[str, Any] = _readings(gaps(ref, [s.tokens for s in chosen]))
    if control:
        ctl_tokens = [np.argmax(x, axis=-1) for x in ctl]
        out = dict(_readings(gaps(ref, ctl_tokens)), served=out)
    return out
