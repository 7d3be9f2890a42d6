"""The comparison that decides ``correct``.

Every answer the window produced is compared with the float64 reference of
its query on the same tables.  Three numbers are compared, each against a
limit of its own:

- ``wrong_exact``: integer cells (group keys, counts) that differ, plus
  every cell of an answer whose columns or row count differ.  Exact: 0.
- ``rel_err``: the largest relative error of any float cell of any answer,
  ``|got - want| / |want|``.  Its limit lies between the largest reading of
  sound runs of the program (float32 sums, chunked) and the smallest
  reading of the control (the reference computed in bfloat16); PERF.md
  gives both readings.
- ``missing``: queries of the window that raised instead of answering.  0.
"""

from __future__ import annotations

from typing import Dict, Mapping, Sequence

import numpy as np

#: ``rel_err``: sound runs read at most 1.76e-6 and the control at least
#: 3.64e-4 (PERF.md, Findings); the limit leaves 28x above the one, 7x below
#: the other
LIMITS = {"wrong_exact": 0, "rel_err": 5e-5, "missing": 0}


def _in_key_order(d: Mapping[str, np.ndarray], keys: Sequence[str]):
    if not keys:
        return {k: np.asarray(v) for k, v in d.items()}
    order = np.lexsort([np.asarray(d[k]) for k in reversed(keys)])
    return {k: np.asarray(v)[order] for k, v in d.items()}


def compare(got: Mapping[str, np.ndarray], want: Mapping[str, np.ndarray],
            keys: Sequence[str] = ()) -> Dict[str, float]:
    """``wrong_exact`` and ``rel_err`` of one answer against its reference."""
    size = sum(np.asarray(w).size for w in want.values())
    if not set(want) <= set(got) or any(
            np.asarray(got[k]).shape != np.asarray(w).shape
            for k, w in want.items()):
        return {"wrong_exact": size, "rel_err": 0.0}
    got, want = _in_key_order(got, keys), _in_key_order(want, keys)
    wrong, rel = 0, 0.0
    for k, w in want.items():
        g = got[k]
        if np.issubdtype(w.dtype, np.integer):
            wrong += int(np.sum(g.astype(np.int64) != w.astype(np.int64)))
        else:
            w = w.astype(np.float64)
            err = np.abs(g.astype(np.float64) - w)
            scale = np.where(w != 0, np.abs(w), 1.0)
            worst = float(np.max(err / scale, initial=0.0))
            # a NaN or infinite answer is as wrong as a float can say
            rel = max(rel, worst if np.isfinite(worst) else np.finfo(np.float64).max)
    return {"wrong_exact": wrong, "rel_err": rel}


def judge(answers, wants, group_keys, missing: int) -> Dict[str, Dict[str, float]]:
    """Compare every ``(query, answer)`` with ``wants[query]``; returns each
    number beside its limit."""
    total = {"wrong_exact": 0, "rel_err": 0.0, "missing": missing}
    for q, got in answers:
        one = compare(got, wants[q], group_keys.get(q, ()))
        total["wrong_exact"] += one["wrong_exact"]
        total["rel_err"] = max(total["rel_err"], one["rel_err"])
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in total.items()}


def passed(checks: Mapping[str, Mapping[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
