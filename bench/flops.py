"""What every model's counts share: the chip's peaks, the causal pairs of a
prefill, the roofline's least time, and the record of one kernel.

The counts of each architecture live in its model module
(``bench/models/<m>.py``): useful FLOPs of an admission segment and of a
decode token, and per kernel its layers call, the step that runs it, how
many calls a step makes and the FLOPs and bytes of one call.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Callable, Tuple

BYTES = 2  # bf16

PEAKS = Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Kernel:
    """A kernel a model's layers call: the harness step that runs it
    (``"admit"`` or ``"decode"``), ``calls(dims)`` calls of it in one
    execution of that step's program, and ``call(dims, work)`` the
    (operations, bytes) one call needs, ``work`` being the step's
    (matched, n_new) segments for an admission, its live lengths for a
    decode step."""

    step: str
    calls: Callable[[object], int]
    call: Callable[[object, object], Tuple[int, int]]


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in ``peaks.json`` is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def causal_pairs(matched: int, n_new: int) -> int:
    """(query, key) pairs of ``n_new`` queries behind ``matched`` positions."""
    return n_new * matched + n_new * (n_new + 1) // 2


def least_time(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The roofline's least time for a call, and which bound sets it."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
