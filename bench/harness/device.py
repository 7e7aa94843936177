"""The chip: refusal of anything that is not one, its peaks, its clocks.

``CompileClock`` and the peak-memory read are copies of ``chip_smoke.py``'s.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Any, Dict

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(SystemExit):
    """Raised before anything is measured when the chips are missing."""


def require_tpu(chips: int):
    """The devices of a TPU with at least ``chips`` chips, or exit non-zero
    with no result.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"[bench] refused: this cell needs {chips} TPU chip(s); JAX "
              f"found {len(devs)} device(s) of platform "
              f"{devs[0].platform!r}", file=sys.stderr, flush=True)
        raise NoChip(3)
    return devs[:chips]


def device_info(devs) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(kind: str) -> Dict[str, float]:
    """The published peaks of device kind ``kind``; an unknown kind is an
    error, not a default."""
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS.name}")
    return table[kind]


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip since the process started."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache reads
    included), and how many there were."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.total = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: object) -> None:
        if event == self.EVENT:
            self.total += duration
            self.count += 1
