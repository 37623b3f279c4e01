"""The comparison that decides ``correct``.

After the window, a sample of the window's lanes drawn from the seed is
simulated again by the plain reference (``bench/reference/ds3.py``) on the
same design, trace, scheduler and governor, and each statistic the program
returned for that lane is compared with the reference's:

* ``latency_rel_err``, ``makespan_rel_err``, ``energy_rel_err``: the
  largest relative gap over the sampled lanes;
* ``peak_temp_err_c``: the largest absolute gap in peak temperature (C);
* ``nonfinite_lanes``: lanes of the whole window with a non-finite
  statistic (exact: limit 0).

Each number is printed beside its limit from ``bench/limits/<cell>.json``;
a number with no limit there fails the run.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import traffic as tr
from .entries import Call

REL = {"avg_latency_us": "latency_rel_err", "makespan_us": "makespan_rel_err",
       "energy_j": "energy_rel_err"}
ABS = {"peak_temp_c": "peak_temp_err_c"}


def sample_lanes(calls: Sequence[Call], k: int, seed: int
                 ) -> List[Tuple[int, int]]:
    """``k`` (call, lane) pairs drawn without replacement from the seed's
    check stream."""
    pairs = [(ci, li) for ci, c in enumerate(calls)
             for li in range(len(c.lanes))]
    rng = np.random.default_rng(tr.stream_seed(seed, tr.CHECK))
    pick = rng.choice(len(pairs), size=min(k, len(pairs)), replace=False)
    return [pairs[int(p)] for p in sorted(pick)]


def references(ref, calls: Sequence[Call], picks
               ) -> Dict[Tuple[int, int], Dict[str, float]]:
    """The reference's statistics of each picked (call, lane);
    ``ref(lane)`` is the cell's entry's reference."""
    return {p: ref(calls[p[0]].lanes[p[1]]) for p in picks}


def gaps(refs: Dict[Tuple[int, int], Dict[str, float]],
         stats: Sequence[Dict[str, np.ndarray]]) -> Dict[str, float]:
    """Worst gap per statistic between ``stats`` (the program's, or the
    control's) and the reference on the picked lanes."""
    worst: Dict[str, float] = {}
    for (ci, li), ref in refs.items():
        for key, got in stats[ci].items():
            name = REL.get(key) or ABS[key]
            err = abs(float(got[li]) - ref[key])
            if key in REL:
                err /= max(abs(ref[key]), 1e-30)
            if not np.isfinite(err):
                err = float("inf")
            worst[name] = max(worst.get(name, 0.0), err)
    return worst


def control_stats(ref, calls: Sequence[Call], picks, dtype,
                  keys: Sequence[str]) -> List[Dict[str, np.ndarray]]:
    """The reference computed at ``dtype`` put in the program's place: the
    statistics ``keys`` it gives for the picked lanes (others left NaN,
    never read)."""
    out = [{k: np.full(len(c.lanes), np.nan) for k in keys} for c in calls]
    for ci, li in picks:
        r = ref(calls[ci].lanes[li], time_dtype=dtype, value_dtype=dtype)
        for k in keys:
            out[ci][k][li] = r[k]
    return out


def nonfinite(stats: Sequence[Dict[str, np.ndarray]]) -> int:
    bad = 0
    for s in stats:
        lanes = len(next(iter(s.values())))
        m = np.zeros(lanes, bool)
        for v in s.values():
            m |= ~np.isfinite(v)
        bad += int(m.sum())
    return bad


def load_limits(root: Path, cell: str) -> Dict[str, float]:
    path = root / "bench" / "limits" / f"{cell}.json"
    if not path.exists():
        return {}
    return {k: float(v) for k, v in json.loads(path.read_text())
            ["limits"].items()}


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, Optional[float]]]]:
    """Each number beside its limit; ``correct`` when every number is at or
    under a limit it has."""
    table = {n: {"value": float(v), "limit": limits.get(n)}
             for n, v in numbers.items()}
    ok = all(t["limit"] is not None and t["value"] <= t["limit"]
             for t in table.values())
    return ok, table
