"""The comparison that decides ``correct``.

Every request served in the window is compared with the plain reference
run on its image: the widest gap between a served logit and the
reference's, over the largest reference logit of that request. The limit
of each number compared comes from the configuration's ``check`` entry.
"""
from __future__ import annotations

import numpy as np


def row_rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per row: max |got - want| / max |want|."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.max(np.abs(want), axis=1), 1e-30)
    return np.max(np.abs(got - want), axis=1) / scale


def unanswered(logits: list, n: int, classes: int) -> int:
    """Requests with no finite (classes,) logits row."""
    bad = 0
    for k in range(n):
        row = logits[k] if k < len(logits) else None
        if (row is None or np.shape(row) != (classes,)
                or not np.all(np.isfinite(row))):
            bad += 1
    return bad


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(every reading within its limit, {name: {"value", "limit"}})."""
    checks = {name: {"value": float(v), "limit": float(limits[name])}
              for name, v in readings.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
