"""Traffic generation, owned by the benchmark: job traces and design
samples drawn from ``--seed`` and the call index.

The Poisson generator is a copy of the program's ``core/jobgen.py``
``poisson_trace`` and the design sampler a copy of ``dse/space.py``'s
latin-hypercube and uniform samplers, so that the yardstick does not move
when the program changes.  The program receives these arrays as they are
(``JobTrace``s), so its own generator is not on the timed path.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# seed-stream namespaces: the warm-up, the window and the check draw from
# disjoint streams of one --seed
WARM, WINDOW, CHECK = 1, 0, 2


def stream_seed(seed: int, *path: int) -> int:
    """A 32-bit seed for one trace or sample, derived from the run's
    ``--seed`` (any non-negative integer) and a path of indices."""
    return int(np.random.SeedSequence([int(seed), *map(int, path)])
               .generate_state(1)[0])


@dataclasses.dataclass(frozen=True)
class Trace:
    """A job trace: arrival time (us, float32, sorted) and application
    index per job."""
    arrival_us: np.ndarray
    app_index: np.ndarray
    app_names: Tuple[str, ...]
    rate_jobs_per_ms: float
    seed: int


def poisson_trace(rate_jobs_per_ms: float, num_jobs: int,
                  app_names: Sequence[str], seed: int,
                  mix: Optional[Sequence[float]] = None) -> Trace:
    """Exponential inter-arrival gaps at ``rate_jobs_per_ms``; the
    application of each job drawn from ``mix`` (uniform by default)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1000.0 / float(rate_jobs_per_ms),
                           size=num_jobs).astype(np.float32)
    arrivals = np.cumsum(gaps, dtype=np.float32)
    probs = None
    if mix is not None:
        probs = np.asarray(mix, dtype=np.float64)
        probs = probs / probs.sum()
    idx = rng.choice(len(app_names), size=num_jobs, p=probs).astype(np.int32)
    return Trace(arrivals, idx, tuple(app_names), float(rate_jobs_per_ms),
                 int(seed))


def call_traces(seed: int, stream: int, call: int, rates: Sequence[float],
                per_rate: int, num_jobs: int, app_names: Sequence[str],
                mix=None) -> List[Trace]:
    """The fresh traces of one call: ``per_rate`` traces at each rate,
    rate-major."""
    out = []
    for k, rate in enumerate(rates):
        for s in range(per_rate):
            out.append(poisson_trace(
                rate, num_jobs, app_names,
                stream_seed(seed, stream, call, k, s), mix))
    return out


# ------------------------------------------------------------------ designs
def _valid(point: Dict) -> bool:
    """At least one CPU: several tasks run only on CPUs."""
    pes = sum(point[a] for a in ("num_big", "num_little", "num_scr",
                                 "num_fft", "num_vit"))
    return pes > 0 and point["num_big"] + point["num_little"] > 0


def _key(point: Dict, axes: Sequence[str]) -> Tuple:
    return tuple(point[a] for a in axes)


def grid_designs(space: Dict[str, Sequence]) -> List[Dict]:
    """Every valid design of the space, in product order over its axes."""
    axes = list(space)
    out = []
    for values in itertools.product(*(space[a] for a in axes)):
        p = dict(zip(axes, values))
        if _valid(p):
            out.append(p)
    return out


def _sample_random(space: Dict[str, Sequence], n: int, seed: int,
                   exclude: Sequence[Dict]) -> List[Dict]:
    axes = list(space)
    rng = np.random.default_rng(seed)
    seen = {_key(p, axes) for p in exclude}
    out: List[Dict] = []
    sizes = [len(space[a]) for a in axes]
    for _ in range(max(64, 50 * n)):
        if len(out) >= n:
            break
        idx = [int(rng.integers(k)) for k in sizes]
        p = {a: space[a][i] for a, i in zip(axes, idx)}
        if not _valid(p) or _key(p, axes) in seen:
            continue
        seen.add(_key(p, axes))
        out.append(p)
    if len(out) < n:
        pool = [p for p in grid_designs(space) if _key(p, axes) not in seen]
        order = rng.permutation(len(pool))
        out += [pool[i] for i in order[:n - len(out)]]
    return out


def lhs_designs(space: Dict[str, Sequence], n: int, seed: int) -> List[Dict]:
    """Latin-hypercube sample: each axis cut into ``n`` strata, permuted
    independently, mapped onto its discrete values; invalid or duplicate
    rows are topped up with uniform draws (seed + 1)."""
    axes = list(space)
    rng = np.random.default_rng(seed)
    cols = []
    for a in axes:
        vals = space[a]
        strata = rng.permutation(n)
        cols.append([vals[int(s * len(vals) // n)] for s in strata])
    seen = set()
    out: List[Dict] = []
    for row in zip(*cols):
        p = dict(zip(axes, row))
        if not _valid(p) or _key(p, axes) in seen:
            continue
        seen.add(_key(p, axes))
        out.append(p)
    if len(out) < n:
        out += _sample_random(space, n - len(out), seed + 1, exclude=out)
    return out
