"""Per-sampling-window telemetry timelines (DESIGN.md §11).

A :class:`Telemetry` holds ``(W, C)`` arrays — one row per sampling window,
one column per frequency domain (cluster) — of the quantities the DTPM loop
integrates: OPP/frequency, utilisation, realised node power and RC node
temperatures.  Both kernels produce it:

* **ref, dynamic governor** — a :class:`TelemetryRecorder` passed to
  ``simulate(..., telemetry=rec)`` records each window in-loop (the exact
  values the governor feedback saw);
* **jax, dynamic governor** — :func:`jax_dtpm_telemetry` replays the kernel's
  ``_window_step`` as a separate jitted ``lax.scan`` against the final
  schedule state, stacking the carry via the scan's ys.  Windows only close
  once no later commit can overlap them (``start ≥ data_ready ≥ epoch ≥
  window end``), so the replay is value-identical to the in-loop carry and
  the ``telemetry=False`` simulation program stays byte-identical;
* **static governors** (both backends) — the same window observables at the
  governor's fixed OPP, via :func:`ref_static_telemetry` (numpy replay) and
  :func:`jax_static_telemetry`.

Sizes: ``W = ceil(makespan / window)`` (windows whose *start* precedes the
makespan — matching both kernels' tail drain); ``C`` is the domain count
(``simkernel_jax.MIN_DOMAINS`` floor, accel fabric last: zero utilisation,
zero frequency).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import numpy as np

from ..core import thermal as _thermal
from ..core.dvfs import capped_levels
from ..core.power import active_power, idle_power
from ..core.resources import ResourceDB
from .metrics import span

TELEMETRY_SCHEMA = "repro.obs/telemetry/v1"

#: default sampling window for *static*-governor telemetry, where no governor
#: window exists to inherit (matches OndemandGovernor's default)
DEFAULT_WINDOW_US = 50.0


@dataclasses.dataclass(frozen=True)
class Telemetry:
    """Per-window timelines.  All arrays have ``W`` rows (sampling windows,
    window ``w`` covering ``[w·window_us, (w+1)·window_us)``)."""
    window_us: float
    freq_idx: np.ndarray      # (W, C) i32 — OPP index per domain (post-clamp)
    freq_ghz: np.ndarray      # (W, C) f32 — frequency per domain (0 = accel)
    util: np.ndarray          # (W, C) f32 — CPU utilisation per domain
    power_w: np.ndarray       # (W, 3) f32 — realised power per thermal node
    temps_c: np.ndarray       # (W, 4) f32 — RC node temps at window end

    @property
    def num_windows(self) -> int:
        return int(self.freq_idx.shape[0])

    @property
    def num_domains(self) -> int:
        return int(self.freq_idx.shape[1])

    @property
    def time_us(self) -> np.ndarray:
        """Window-end timestamps, (W,)."""
        return (np.arange(self.num_windows, dtype=np.float64) + 1.0) \
            * self.window_us

    @property
    def peak_temp_c(self) -> float:
        """Peak on-chip (non-board) temperature over the timeline."""
        if self.temps_c.size == 0:
            return float(_thermal.T_AMBIENT_C)
        return float(np.max(self.temps_c[:, :3]))

    @property
    def avg_power_w(self) -> float:
        """Mean total (all-node) power over the timeline."""
        if self.power_w.size == 0:
            return 0.0
        return float(np.mean(np.sum(self.power_w, axis=1)))

    def to_dict(self) -> Dict:
        """JSON-ready form (schema-tagged; inverse of :meth:`from_dict`)."""
        return {
            "schema": TELEMETRY_SCHEMA,
            "window_us": float(self.window_us),
            "freq_idx": self.freq_idx.astype(int).tolist(),
            "freq_ghz": np.asarray(self.freq_ghz, np.float64).tolist(),
            "util": np.asarray(self.util, np.float64).tolist(),
            "power_w": np.asarray(self.power_w, np.float64).tolist(),
            "temps_c": np.asarray(self.temps_c, np.float64).tolist(),
        }

    @classmethod
    def from_dict(cls, d: Dict) -> "Telemetry":
        if d.get("schema") != TELEMETRY_SCHEMA:
            raise ValueError(f"not a telemetry dict: schema={d.get('schema')!r}")
        return cls(
            window_us=float(d["window_us"]),
            freq_idx=np.asarray(d["freq_idx"], np.int32),
            freq_ghz=np.asarray(d["freq_ghz"], np.float32),
            util=np.asarray(d["util"], np.float32),
            power_w=np.asarray(d["power_w"], np.float32),
            temps_c=np.asarray(d["temps_c"], np.float32),
        )


class TelemetryRecorder:
    """In-loop per-window recorder for the reference kernel.

    ``simulate(..., telemetry=rec)`` calls :meth:`on_window` once per closed
    sampling window (in order); :meth:`build` assembles the ``(W, C)``
    :class:`Telemetry`, padding domains the SoC doesn't populate (the accel
    fabric column) with zeros.
    """

    def __init__(self, window_us: float):
        self.window_us = float(window_us)
        self._rows: List[Dict] = []

    def on_window(self, w_end_us: float, util: Dict[int, float],
                  freq_ghz: Dict[int, float], freq_idx: Dict[int, int],
                  node_power_w: np.ndarray, temps_c: np.ndarray) -> None:
        self._rows.append(dict(
            w_end_us=float(w_end_us),
            util=dict(util), freq_ghz=dict(freq_ghz),
            freq_idx=dict(freq_idx),
            power_w=np.asarray(node_power_w, np.float32).copy(),
            temps_c=np.asarray(temps_c, np.float32).copy(),
        ))

    def build(self, num_domains: int) -> Telemetry:
        W, C = len(self._rows), int(num_domains)
        freq_idx = np.zeros((W, C), np.int32)
        freq_ghz = np.zeros((W, C), np.float32)
        util = np.zeros((W, C), np.float32)
        power_w = np.zeros((W, _thermal.NUM_NODES), np.float32)
        temps_c = np.full((W, 4), _thermal.T_AMBIENT_C, np.float32)
        for w, row in enumerate(self._rows):
            for c, v in row["util"].items():
                util[w, c] = v
            for c, v in row["freq_ghz"].items():
                freq_ghz[w, c] = v
            for c, v in row["freq_idx"].items():
                freq_idx[w, c] = v
            power_w[w] = row["power_w"]
            temps_c[w] = row["temps_c"]
        return Telemetry(self.window_us, freq_idx, freq_ghz, util,
                         power_w, temps_c)


# --------------------------------------------------------------------------
# Shared sizing / frequency-column helpers
# --------------------------------------------------------------------------

def num_windows_for(makespan_us: float, window_us: float) -> int:
    """Windows whose start precedes the makespan (both kernels' drain)."""
    if makespan_us <= 0.0 or window_us <= 0.0:
        return 0
    return int(math.ceil(makespan_us / window_us - 1e-9))


def _bucket_pow2(n: int) -> int:
    """Next power of two ≥ n — the jit-cache bucket for the window axis, so
    sweeping makespans doesn't recompile the telemetry scan per run."""
    return 1 << max(int(n) - 1, 0).bit_length()


def domain_count(db: ResourceDB) -> int:
    """Frequency-domain count for ``db`` (matches ``build_tables``)."""
    from ..core.simkernel_jax import MIN_DOMAINS
    return max(MIN_DOMAINS, max(pe.cluster for pe in db.pes) + 1)


def static_freq_columns(db: ResourceDB, governor, num_domains: int):
    """``(freq_ghz, freq_idx)`` rows, each ``(C,)``, for a *static* governor:
    one fixed entry per CPU cluster (nearest capped-ladder level), zeros for
    the accel domain.  Constants of the governor — shared by both backends so
    the ref↔jax telemetry comparison is exact by construction."""
    caps = getattr(governor, "freq_caps", None)
    freq_ghz = np.zeros(num_domains, np.float32)
    freq_idx = np.zeros(num_domains, np.int32)
    seen = set()
    for pe in db.pes:
        if not pe.is_cpu or pe.cluster in seen:
            continue
        seen.add(pe.cluster)
        f = governor.initial_freq(pe.pe_type)
        opps = capped_levels(pe.pe_type, caps)
        k = min(range(len(opps)), key=lambda i: abs(opps[i] - f))
        freq_ghz[pe.cluster] = opps[k]
        freq_idx[pe.cluster] = k
    return freq_ghz, freq_idx


# --------------------------------------------------------------------------
# JAX glue — wrap the kernel's jitted telemetry scans
# --------------------------------------------------------------------------

def jax_dtpm_telemetry(tables, gov, out: Dict, app_idx) -> Telemetry:
    """Telemetry for one ``simulate_jax_dtpm`` run.

    ``out`` is the kernel's output dict (needs ``scheduled/start/finish/
    onpe/onopp/makespan_us``).  The window axis is bucketed to the next power
    of two for jit-cache stability and truncated back to the real count.
    """
    import jax
    import jax.numpy as jnp
    from ..core.simkernel_jax import _telemetry_scan_dtpm

    window = float(gov.sample_window_us)
    W = num_windows_for(float(out["makespan_us"]), window)
    if W == 0:
        C = int(tables.opp_freq.shape[0])
        return _empty(window, C)
    with span("repro.launch"):
        ys = _telemetry_scan_dtpm(tables, gov,
                                  jnp.asarray(app_idx, jnp.int32),
                                  out["scheduled"], out["start"],
                                  out["finish"], out["onpe"], out["onopp"],
                                  num_windows=_bucket_pow2(W))
    with span("repro.wait"):
        jax.block_until_ready(ys)
    with span("repro.assemble"):
        freq_idx = np.asarray(ys["opp_idx"])[:W]
        opp_freq = np.asarray(tables.opp_freq)                   # (C, K)
        C = opp_freq.shape[0]
        freq_ghz = opp_freq[np.arange(C)[None, :], freq_idx]     # (W, C)
        return Telemetry(window, freq_idx.astype(np.int32),
                         freq_ghz.astype(np.float32),
                         np.asarray(ys["util"])[:W],
                         np.asarray(ys["power_w"])[:W],
                         np.asarray(ys["temps_c"])[:W])


def jax_static_telemetry(db: ResourceDB, governor, tables, out: Dict,
                         app_idx,
                         window_us: Optional[float] = None) -> Telemetry:
    """Telemetry for one static-governor ``simulate_jax`` run: the window
    observables at the tables' fixed OPP; frequency columns are governor
    constants (see :func:`static_freq_columns`)."""
    import jax
    import jax.numpy as jnp
    from ..core.simkernel_jax import _telemetry_scan_static

    window = float(window_us if window_us is not None
                   else getattr(governor, "sample_window_us", None)
                   or DEFAULT_WINDOW_US)
    C = domain_count(db)
    W = num_windows_for(float(out["makespan_us"]), window)
    if W == 0:
        return _empty(window, C)
    with span("repro.launch"):
        ys = _telemetry_scan_static(tables, jnp.asarray(app_idx, jnp.int32),
                                    out["scheduled"], out["start"],
                                    out["finish"], out["onpe"], window,
                                    num_windows=_bucket_pow2(W),
                                    num_domains=C)
    with span("repro.wait"):
        jax.block_until_ready(ys)
    with span("repro.assemble"):
        f_ghz, f_idx = static_freq_columns(db, governor, C)
        return Telemetry(window,
                         np.broadcast_to(f_idx, (W, C)).copy(),
                         np.broadcast_to(f_ghz, (W, C)).copy(),
                         np.asarray(ys["util"])[:W],
                         np.asarray(ys["power_w"])[:W],
                         np.asarray(ys["temps_c"])[:W])


def _empty(window_us: float, C: int) -> Telemetry:
    return Telemetry(window_us,
                     np.zeros((0, C), np.int32), np.zeros((0, C), np.float32),
                     np.zeros((0, C), np.float32),
                     np.zeros((0, _thermal.NUM_NODES), np.float32),
                     np.zeros((0, 4), np.float32))


# --------------------------------------------------------------------------
# Reference-kernel static replay (numpy)
# --------------------------------------------------------------------------

def ref_static_telemetry(db: ResourceDB, result, governor,
                         window_us: Optional[float] = None) -> Telemetry:
    """Post-hoc telemetry replay of a static-governor reference run: window
    utilisation/power from the realised schedule (``result.records``), RC
    temperatures integrated in real time (dt = window), frequency columns
    from the governor.  Matches :func:`jax_static_telemetry` on comm-free
    traces (asserted in tests/test_obs.py)."""
    window = float(window_us if window_us is not None
                   else getattr(governor, "sample_window_us", None)
                   or DEFAULT_WINDOW_US)
    C = domain_count(db)
    W = num_windows_for(result.makespan_us, window)
    if W == 0:
        return _empty(window, C)

    node_of_pe = _thermal.cluster_nodes(db)
    cl_cpus = np.zeros(C)
    for pe in db.pes:
        if pe.is_cpu:
            cl_cpus[pe.cluster] += 1.0
    p_idle = np.asarray([idle_power(pe) for pe in db.pes])

    util = np.zeros((W, C), np.float32)
    power_w = np.zeros((W, _thermal.NUM_NODES), np.float32)
    temps_c = np.full((W, 4), _thermal.T_AMBIENT_C, np.float32)
    rc_ab = _thermal.exact_step_matrices(window * 1e-6)
    temps = np.full(4, _thermal.T_AMBIENT_C)
    for w in range(W):
        w0, w1 = w * window, (w + 1) * window
        busy = np.zeros(db.num_pes)
        p = np.zeros(_thermal.NUM_NODES)
        for r in result.records:
            ov = max(0.0, min(r.finish_us, w1) - max(r.start_us, w0))
            if ov <= 0.0:
                continue
            pe = db.pes[r.pe_id]
            busy[r.pe_id] += ov
            p[node_of_pe[r.pe_id]] += active_power(pe, r.freq_ghz) * ov / window
            if pe.is_cpu:
                util[w, pe.cluster] += ov
        util[w] /= np.maximum(window * cl_cpus, 1e-9)
        idle_frac = 1.0 - np.clip(busy / window, 0.0, 1.0)
        for j in range(db.num_pes):
            p[node_of_pe[j]] += p_idle[j] * idle_frac[j]
        temps = _thermal.exact_step(temps, p, *rc_ab)
        power_w[w] = p
        temps_c[w] = temps

    f_ghz, f_idx = static_freq_columns(db, governor, C)
    return Telemetry(window,
                     np.broadcast_to(f_idx, (W, C)).copy(),
                     np.broadcast_to(f_ghz, (W, C)).copy(),
                     util, power_w, temps_c)
