"""Kernel-dispatch profiling: which mpGEMM config actually executed.

``kernels.ops.resolve_dispatch`` is the single trace-time decision point
for every Pallas mpGEMM a jitted program contains; ``core.lmma.
select_fusion`` is the VMEM-fit heuristic under it. Both call ``record()``
here — a no-op unless a :class:`DispatchRecorder` is active — so a serve
run can dump exactly which (shape-key, fusion, blocks) dispatched, whether
the decision came from the measured tuning cache or the heuristic, per
traced program.

This mirrors the per-kernel visibility T-MAC / LUT-GEMM use for their
mpGEMM breakdown tables: aggregate tok/s can hide one projection silently
falling back to the staged path; the dispatch log cannot.

The hooks run at TRACE time (host python, once per compiled program), never
inside compiled code — recording costs nothing per decode step. The module
is dependency-free so the kernels/core layers can import it without cycles.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Tuple

__all__ = ["DispatchRecord", "DispatchRecorder", "enable", "disable",
           "get_active", "record", "recording"]


@dataclasses.dataclass
class DispatchRecord:
    """One deduplicated dispatch decision (+ how often it was traced)."""

    kind: str            # "dispatch" (resolve_dispatch) | "select_fusion"
    key: str             # autotune.shape_key / lmma descriptor name
    fusion: str          # resolved fusion actually dispatched
    requested: str       # caller policy: auto | tuned | fused | staged,
    #                      or lut_xla (its lookup kernel over packed planes)
    source: str          # "tuned" (cache hit) | "heuristic" | "forced"
    block_m: int = 0
    block_n: int = 0
    block_g: int = 0
    count: int = 1       # times this exact decision was traced

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class DispatchRecorder:
    def __init__(self):
        self._lock = threading.Lock()
        self._records: Dict[Tuple, DispatchRecord] = {}

    def record(self, kind: str, key: str, fusion: str, requested: str,
               source: str, blocks: Tuple[int, int, int] = (0, 0, 0)):
        k = (kind, key, fusion, requested, source, tuple(blocks))
        with self._lock:
            rec = self._records.get(k)
            if rec is None:
                self._records[k] = DispatchRecord(
                    kind, key, fusion, requested, source,
                    blocks[0], blocks[1], blocks[2])
            else:
                rec.count += 1

    def records(self, kind: Optional[str] = None) -> List[DispatchRecord]:
        with self._lock:
            recs = list(self._records.values())
        if kind is not None:
            recs = [r for r in recs if r.kind == kind]
        return sorted(recs, key=lambda r: (r.kind, r.key, r.fusion))

    def summary(self) -> dict:
        """Aggregate for stats()/bench JSON: decisions by source, the
        mpGEMM calls traced onto lut_xla's lookup kernel, and the full
        per-shape table."""
        recs = self.records()
        disp = [r for r in recs if r.kind == "dispatch"]
        return {
            "decisions": len(disp),
            "tuned": sum(1 for r in disp if r.source == "tuned"),
            "heuristic": sum(1 for r in disp if r.source == "heuristic"),
            "forced": sum(1 for r in disp if r.source == "forced"),
            "lut_xla": sum(r.count for r in disp if r.requested == "lut_xla"),
            "records": [r.as_dict() for r in recs],
        }

    def clear(self):
        with self._lock:
            self._records.clear()

    def __len__(self):
        with self._lock:
            return len(self._records)


_ACTIVE: Optional[DispatchRecorder] = None
_GUARD = threading.Lock()


def enable(recorder: Optional[DispatchRecorder] = None) -> DispatchRecorder:
    """Install (and return) the active recorder; idempotent if one is
    already active and none is supplied."""
    global _ACTIVE
    with _GUARD:
        if recorder is not None:
            _ACTIVE = recorder
        elif _ACTIVE is None:
            _ACTIVE = DispatchRecorder()
        return _ACTIVE


def disable():
    global _ACTIVE
    with _GUARD:
        _ACTIVE = None


def get_active() -> Optional[DispatchRecorder]:
    return _ACTIVE


def record(kind: str, key: str, fusion: str, requested: str, source: str,
           blocks: Tuple[int, int, int] = (0, 0, 0)):
    """Module-level hook for ops/lmma: single ``is None`` check when
    profiling is off."""
    rec = _ACTIVE
    if rec is not None:
        rec.record(kind, key, fusion, requested, source, blocks)


class recording:
    """Context manager: install a fresh recorder, restore the prior one."""

    def __enter__(self) -> DispatchRecorder:
        self._prev = get_active()
        self._rec = DispatchRecorder()
        enable(self._rec)
        return self._rec

    def __exit__(self, *exc):
        global _ACTIVE
        with _GUARD:
            _ACTIVE = self._prev
        return False
