"""The profiler trace of a traced window, reduced to what the per-layer
metrics read.

``capture`` runs the window under ``jax.profiler`` with the Python tracer
off (it slows the host several-fold) and reads the ``.xplane.pb`` with
``jax.profiler.ProfileData``.  What is kept is a :class:`TraceView`, which
also round-trips through JSON so that the reduction can be checked on a
small recorded trace (``bench/tests/data``):

* ``programs``: per device plane, every XLA program execution (the
  ``XLA Modules`` line), as ``(program, start_ns, end_ns)`` with the
  program's hash suffix dropped (``jit__sweep_grid(123…)`` →
  ``jit__sweep_grid``);
* ``spans``: the benchmark's own host spans (``bench.*``
  ``TraceAnnotation``s), on the same clock.

Device busy time is the union of a device's program intervals.
"""
from __future__ import annotations

import dataclasses
import glob
import re
import shutil
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class TraceView:
    window: Interval
    programs: Dict[str, List[Tuple[str, float, float]]]
    spans: List[Tuple[str, float, float]]

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: Dict) -> "TraceView":
        return cls(tuple(d["window"]),
                   {k: [tuple(e) for e in v] for k, v in d["programs"].items()},
                   [tuple(s) for s in d["spans"]])

    # -------------------------------------------------------- derived
    @property
    def devices(self) -> List[str]:
        return sorted(self.programs)

    def busy(self, device: str) -> List[Interval]:
        """Merged intervals in which a program ran on ``device``, clipped
        to the window."""
        return clip(union((s, e) for _, s, e in self.programs[device]),
                    *self.window)

    def calls(self) -> List[Interval]:
        return sorted((s, e) for n, s, e in self.spans if n == "bench.call")

    def span_intervals(self, name: str) -> List[Interval]:
        return sorted((s, e) for n, s, e in self.spans if n == name)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Time within ``[lo, hi]`` that the merged intervals cover."""
    return total(clip(merged, lo, hi))


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The uncovered parts of ``[lo, hi]``."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Sequence[Tuple[str, float, float]], t: float
              ) -> Optional[str]:
    """The shortest benchmark span that contains time ``t``."""
    best = None
    for n, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return None if best is None else best[0]


def split_by_spans(spans: Sequence[Tuple[str, float, float]],
                   lo: float, hi: float) -> List[Tuple[str, float]]:
    """``[lo, hi]`` cut at every span boundary inside it, each piece
    labelled by the innermost benchmark span that covers it."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    return [(innermost(spans, 0.5 * (a + b)) or "outside spans", b - a)
            for a, b in zip(cuts, cuts[1:])]


def breakdown(view: TraceView, top: int = 10) -> Dict[str, List]:
    """The programs that took most device time, and the device's idle
    time in the window by the benchmark span the host was in: seconds,
    mean over devices, at most ``top`` entries each."""
    n = max(len(view.devices), 1)
    lo, hi = view.window
    per_prog: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for dev in view.devices:
        for name, s, e in view.programs[dev]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_prog[name] = per_prog.get(name, 0.0) + d * 1e-9 / n
        for s, e in gaps(view.busy(dev), lo, hi):
            for label, d in split_by_spans(view.spans, s, e):
                idle[label] = idle.get(label, 0.0) + d * 1e-9 / n

    def rank(d):
        return sorted(([k, v] for k, v in d.items()),
                      key=lambda kv: -kv[1])[:top]

    return {"device_ops": rank(per_prog), "idle_gaps": rank(idle)}


def plane_name(device_id: int) -> str:
    return f"/device:TPU:{device_id}"


def select_devices(programs: Dict[str, List[Tuple[str, float, float]]],
                   device_ids: Sequence[int]
                   ) -> Dict[str, List[Tuple[str, float, float]]]:
    """The planes of the cell's own devices, and only those: a device of
    the cell that ran nothing is kept (idle), one outside it is dropped."""
    return {plane_name(i): list(programs.get(plane_name(i), []))
            for i in device_ids}


def read_xplane(path: Path, device_ids: Sequence[int],
                window_span: str = "bench.window") -> TraceView:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    programs: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            progs = programs.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    progs.extend((_HASH.sub("", e.name), e.start_ns, e.end_ns)
                                 for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith("bench."))
    win = [(s, e) for n, s, e in spans if n == window_span]
    if len(win) != 1:
        raise RuntimeError(f"trace holds {len(win)} {window_span} spans")
    return TraceView(win[0], select_devices(programs, device_ids),
                     sorted(spans, key=lambda x: x[1]))


class Capture:
    """``with Capture(dir, ids) as cap:`` traces the block; ``cap.view`` is
    the reduced trace of the devices ``ids`` once the block has closed.
    The raw trace directory is deleted after reading."""

    def __init__(self, directory: Path, device_ids: Sequence[int]):
        self.dir = Path(directory)
        self.device_ids = list(device_ids)
        self.view: Optional[TraceView] = None

    def __enter__(self) -> "Capture":
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        return self

    def __exit__(self, *exc) -> bool:
        import jax
        jax.profiler.stop_trace()
        if exc[0] is None:
            paths = sorted(glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                                     recursive=True))
            self.view = read_xplane(Path(paths[-1]), self.device_ids)
        shutil.rmtree(self.dir, ignore_errors=True)
        return False
