"""Named counters and timers + the run manifest.

One process-wide registry replaces ad-hoc instrumentation state that was
scattered through the codebase (the one-time ``compile_count = [0]``
mutable-list hack in ``repro.scenario.sweep``, whose deprecated ``[0]``
alias is now gone, and per-benchmark ``perf_counter`` pairs).  Counters
and timers are cheap plain-python objects — they are incremented inside
jitted python bodies (which run only on trace), so they count *compiles*,
never per-step work.

:func:`run_manifest` snapshots the registry plus the execution environment
(device/platform, versions, scenario hash) into a JSON-ready dict — attached
to every ``Result`` and every ``BENCH_*.json`` so perf artifacts are
self-describing.

:func:`span` names a stretch of host code on the profiler's clock
(``jax.profiler.TraceAnnotation``), so a device trace shows what the host
was doing while the device waited (DESIGN.md §11).

This module is stdlib-only at import time (JAX is imported lazily inside
``span`` and ``run_manifest``): the simulation kernels import it for their
compile counters, so it must not import them back.
"""
from __future__ import annotations

import functools
import hashlib
import platform as _platform
import time
from typing import Dict, Optional

MANIFEST_SCHEMA = "repro.obs/manifest/v1"


class Counter:
    """A named counter (``.value`` / ``.inc()`` / ``.set()`` / ``.reset()``).

    The legacy one-element-list protocol (``c[0]``), deprecated when the
    registry replaced the ``compile_count = [0]`` hack and kept for one
    release, has been removed.
    """
    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value = 0

    @property
    def value(self) -> int:
        return self._value

    def inc(self, n: int = 1) -> int:
        self._value += n
        return self._value

    def reset(self) -> None:
        self._value = 0

    def set(self, n: int) -> int:
        """Hold ``n`` (a counter that reports the latest state, not a
        total)."""
        self._value = int(n)
        return self._value

    def __int__(self) -> int:
        return self._value

    def __repr__(self) -> str:
        return f"Counter({self.name}={self._value})"


class Timer:
    """A reusable wall-clock timer (``time.perf_counter``) context manager.

    ``with t: ...`` accumulates into ``total_s``/``count`` and exposes the
    most recent interval as ``last_s`` — the one shape every benchmark's
    cold/warm timing boilerplate reduces to.
    """
    __slots__ = ("name", "count", "total_s", "last_s", "_t0")

    def __init__(self, name: Optional[str] = None):
        self.name = name
        self.reset()

    def reset(self) -> None:
        self.count = 0
        self.total_s = 0.0
        self.last_s = 0.0

    def __enter__(self) -> "Timer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.last_s = time.perf_counter() - self._t0
        self.total_s += self.last_s
        self.count += 1
        return False

    @property
    def last_us(self) -> float:
        return self.last_s * 1e6

    @property
    def avg_s(self) -> float:
        return self.total_s / max(self.count, 1)

    def __repr__(self) -> str:
        return (f"Timer({self.name}: n={self.count}, "
                f"total={self.total_s:.6f}s, last={self.last_s:.6f}s)")


_COUNTERS: Dict[str, Counter] = {}
_TIMERS: Dict[str, Timer] = {}


def counter(name: str) -> Counter:
    """The registered counter ``name`` (created on first use)."""
    c = _COUNTERS.get(name)
    if c is None:
        c = _COUNTERS[name] = Counter(name)
    return c


def timer(name: str) -> Timer:
    """The registered timer ``name`` (created on first use)."""
    t = _TIMERS.get(name)
    if t is None:
        t = _TIMERS[name] = Timer(name)
    return t


@functools.lru_cache(maxsize=None)
def _trace_annotation():
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def span(name: str):
    """``with span("repro.<layer>[.<what>]"): ...`` — a host span on the
    profiler's clock (``jax.profiler.TraceAnnotation``).  It records only
    while a profiler trace is running and costs about half a microsecond
    when none is (DESIGN.md §11).  Host code only: inside a jitted body it would record once
    per compile, not per call (lint JX003).  Spans nest by time on the
    calling thread; put them at stage boundaries, never per design or per
    task in a loop."""
    return _trace_annotation()(name)


def snapshot() -> Dict[str, Dict[str, float]]:
    """JSON-ready registry state: counter values + timer totals."""
    return {
        "counters": {n: c.value for n, c in sorted(_COUNTERS.items())},
        "timers": {n: {"count": t.count, "total_s": t.total_s,
                       "last_s": t.last_s}
                   for n, t in sorted(_TIMERS.items())},
    }


def reset_all() -> None:
    for c in _COUNTERS.values():
        c.reset()
    for t in _TIMERS.values():
        t.reset()


def scenario_hash(scenario) -> str:
    """Stable short hash of a frozen Scenario (its dataclass repr is
    deterministic), usable to correlate runs across processes/artifacts."""
    return hashlib.sha1(repr(scenario).encode()).hexdigest()[:12]


def jit_compile_count() -> int:
    """Total jitted-program traces recorded by the kernel/sweep counters."""
    return sum(c.value for n, c in _COUNTERS.items()
               if n.endswith("compile_count"))


def run_manifest(scenario=None, backend: Optional[str] = None,
                 **extra) -> Dict:
    """A self-describing record of one run: what ran, where, how compiled.

    Fields: schema tag, UTC timestamp, python/JAX versions, device platform,
    kind and count (an error from JAX's device query propagates), total jit
    compile count plus the full counter/timer snapshot,
    and — when given — the scenario label/hash and backend.  ``extra``
    key-values (wall times, bench name, …) are merged verbatim.
    """
    from datetime import datetime, timezone
    man = {
        "schema": MANIFEST_SCHEMA,
        "timestamp": datetime.now(timezone.utc).isoformat(timespec="seconds"),
        "python": _platform.python_version(),
        "host_platform": _platform.platform(),
    }
    # no fallback: the manifest names the device the run used, or the
    # backend's error propagates
    import jax
    man["jax_version"] = jax.__version__
    man["device_platform"] = jax.default_backend()
    man["device_kind"] = jax.devices()[0].device_kind
    man["device_count"] = jax.device_count()
    if scenario is not None:
        man["scenario"] = scenario.label()
        man["scenario_hash"] = scenario_hash(scenario)
    if backend is not None:
        man["backend"] = backend
    man["jit_compile_count"] = jit_compile_count()
    man["metrics"] = snapshot()
    man.update(extra)
    return man
