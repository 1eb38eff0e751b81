"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the requests the run finished is
drawn from the seed, the longest of them always in it, until it holds at
least ``MIN_SERVED`` served tokens.  The reference runs once over each
sampled prompt (document + question) followed by its served tokens, and for
every served token reads the gap by which the reference's logit of that
token lies below the reference's best logit at the same position.  The
number compared is the widest such gap (``max_logit_gap``); greedy serving
of a sound program keeps it at the size of bf16 rounding.

The reference is the configuration's model module's ``logits``
(``bench/models/<m>.py``).  The negative control puts the reference itself
in the program's place, at the nearest precision below bf16 (float8 e4m3,
``reference.FP8``): at each position of the same sequences it reads the
gap of the token the control puts first.

Beside them, every check reads the planted fault of a token altered where
it is produced, with the reference in the program's place: a decode step
that served the runner-up token reads, at its position, the reference's
margin between its best and second-best logits
(``runner_up_max_logit_gap``, the widest over the sample's decode
positions).  It is printed, not judged: it says how far a wrong token
lies above the limit.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

from bench import reference
from bench.traffic import seed_rng

MIN_SERVED = 320
LIMITS_DIR = Path(__file__).resolve().parent / "limits"


@dataclasses.dataclass
class Served:
    """One finished request: its prompt and the tokens the program served."""

    req: int
    prompt: List[int]
    tokens: List[int]


def sample(finished: Sequence[Served], seed: int, min_served: int = MIN_SERVED) -> List[Served]:
    """The longest finished request, then others drawn from the seed, until
    the sample holds ``min_served`` served tokens (or every request)."""
    if not finished:
        return []
    longest = max(finished, key=lambda s: (len(s.prompt) + len(s.tokens), s.req))
    rest = [s for s in finished if s is not longest]
    order = seed_rng(seed ^ 0x5EED).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= min_served:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def served_gaps(ref_logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """Per served token: reference best logit minus the served token's."""
    idx = np.arange(len(tokens))
    return ref_logits.max(axis=1) - ref_logits[idx, np.asarray(tokens)]


def runner_up_gaps(ref_logits: np.ndarray) -> np.ndarray:
    """Per position: the gap of the reference's second-best token."""
    top2 = np.partition(ref_logits, -2, axis=1)[:, -2:]
    return top2[:, 1] - top2[:, 0]


def control_gaps(ref_logits: np.ndarray, control_logits: np.ndarray) -> np.ndarray:
    """Per position: the gap of the token the control puts first."""
    return served_gaps(ref_logits, control_logits.argmax(axis=1))


def readings(model, weights: dict, d, picked: Sequence[Served], control: bool = False) -> Dict:
    """The program's widest gap over the sample, and with ``control`` the
    control's at the same positions; ``model`` is the configuration's model
    module, whose ``logits`` is the plain reference, at sizes ``d``."""
    prog, ctrl, fault = [], [], []
    for s in picked:
        seq = s.prompt + s.tokens[:-1]
        rows = reference.served_rows(len(s.prompt), len(s.tokens))
        ref = model.logits(weights, d, seq, rows)
        prog.append(served_gaps(ref, s.tokens))
        fault.append(runner_up_gaps(ref[1:]))
        if control:
            low = model.logits(weights, d, seq, rows, fmt=reference.FP8)
            ctrl.append(control_gaps(ref, low))
    out = {"max_logit_gap": float(np.concatenate(prog).max()),
           "runner_up_max_logit_gap": float(np.concatenate(fault).max(initial=0.0)),
           "served_tokens": int(sum(len(s.tokens) for s in picked)),
           "requests": len(picked)}
    if control:
        out["control_max_logit_gap"] = float(np.concatenate(ctrl).max())
    return out


def limits(workload: str, directory: Path = LIMITS_DIR) -> Dict[str, float]:
    """The cell's limits, from ``bench/limits/<workload>.json``."""
    return json.loads((directory / f"{workload}.json").read_text())["limits"]


def judge(read: Dict, lim: Dict[str, float]) -> bool:
    """Correct when every compared number is at or under its limit."""
    return all(read[k] <= v for k, v in lim.items())
