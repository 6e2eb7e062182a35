"""What one run of a cell produced, and how it is printed.

Standard output ends with one JSON object; the numbers compared with the
reference, each beside its limit, come last in it (``compared``) and as
the last lines of standard error.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class Run:
    setup_s: float
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int]
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    compared: Dict[str, Tuple[float, float]] = dataclasses.field(
        default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)
    ctx: dict = dataclasses.field(default_factory=dict)

    def compare(self, name: str, value: float, limit: float) -> None:
        """Record a number that must not exceed its limit."""
        self.compared[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(
            v <= lim for v, lim in self.compared.values())


def emit(run: Run, metrics: Dict[str, dict], device: dict,
         breakdown: Optional[dict] = None, out=None, err=None) -> None:
    """Print the notes, the compared numbers and the result line."""
    out = out or sys.stdout
    err = err or sys.stderr
    for note in run.notes:
        print(f"bench: {note}", file=err)
    compared = {k: {"value": v, "limit": lim}
                for k, (v, lim) in run.compared.items()}
    for k, c in compared.items():
        print(f"compared {k}={c['value']!r} limit={c['limit']!r}", file=err)
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    err.flush()
    print(json.dumps(line), file=out, flush=True)
