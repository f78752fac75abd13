"""The comparison that decides ``correct``: each number beside its limit.

Gaps are measured the same way for every cell: a *relative sup gap*
``max|got - want| / max|want|`` for arrays whose elements are O(1) answers
(scores, scalers, thresholds), and for weights the gap between two norms,
per leaf, against the reference's norm of that leaf or of the member's
median leaf, whichever is larger.
"""

import sys
from typing import Dict, List

import numpy as np


def sup_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def rel_l2_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def worst_leaf_norm_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> float:
    """Worst leaf of ``| ||got|| - ||want|| |`` over
    ``max(||want||, median leaf's ||want||)``."""
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in want.items()}
    median = float(np.median(list(norms.values())))
    worst = 0.0
    for k, n_want in norms.items():
        n_got = float(np.linalg.norm(np.asarray(got[k], np.float64)))
        if not np.isfinite(n_got):
            return float("inf")
        worst = max(worst, abs(n_got - n_want) / max(n_want, median, 1e-30))
    return worst


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    """``{name: {"value", "limit", "ok"}}`` for every limit of the cell; a
    number the run did not produce fails."""
    out = {}
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = value is not None and np.isfinite(value) and value <= limit
        out[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    for name, value in numbers.items():
        if name not in out:  # reported for the record, held to nothing
            out[name] = {"value": value, "limit": None, "ok": True}
    return out


def report(checks: Dict[str, dict]) -> List[str]:
    lines = [
        f"check {name}: {c['value']!r} (limit {c['limit']!r}) {'ok' if c['ok'] else 'FAILED'}"
        for name, c in checks.items()
    ]
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    return lines


def is_correct(checks: Dict[str, dict]) -> bool:
    return all(c["ok"] for c in checks.values())
