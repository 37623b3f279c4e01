"""Batched RC thermal co-simulation (JAX port of ``repro.core.thermal``).

Same lumped network — nodes [big, LITTLE, accel fabric] coupled through a
board node to ambient — integrated with forward Euler under ``lax.scan`` so
peak temperature evaluates for every (design, trace) pair inside the same
``jit`` as the schedule simulation.

Pipeline:
  1. ``binned_power_trace``   — time-bin each realised schedule
     (start/finish/onpe from the sim kernel) into a (K, 3) per-node power
     trace: active power while a PE runs, idle leakage otherwise.
  2. ``peak_temperature``     — treat the trace as one period of a sustained
     (streaming) workload: warm-start from the analytical steady state of
     the period-mean power, then scan a few periods at the real time step to
     capture the intra-period ripple.  Linear RC + period ≪ thermal time
     constants ⇒ this is the converged periodic response, at O(K·repeats)
     cost instead of integrating seconds of transient.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import thermal as _ref
from ..core.simkernel_jax import fixed_order_sum

T_AMBIENT_C = jnp.float32(_ref.T_AMBIENT_C)
R_TO_BOARD = jnp.asarray(_ref.R_TO_BOARD, jnp.float32)     # (3,) K/W
C_NODE = jnp.asarray(_ref.C_NODE, jnp.float32)             # (3,) J/K
R_BOARD_AMB = jnp.float32(_ref.R_BOARD_AMB)
C_BOARD = jnp.float32(_ref.C_BOARD)


def steady_state(power_w: jnp.ndarray) -> jnp.ndarray:
    """Analytical steady state for constant (3,) node power -> (4,) temps."""
    tb = T_AMBIENT_C + R_BOARD_AMB * fixed_order_sum(power_w)
    return jnp.concatenate([tb + R_TO_BOARD * power_w, tb[None]])


def euler_step(temps: jnp.ndarray, power_w: jnp.ndarray,
               dt_s: jnp.ndarray) -> jnp.ndarray:
    """One forward-Euler step on the (4,) [nodes..., board] state."""
    t_node, t_board = temps[:3], temps[3]
    flow = (t_node - t_board) / R_TO_BOARD
    t_node = t_node + dt_s / C_NODE * (power_w - flow)
    t_board = t_board + dt_s / C_BOARD * (
        jnp.sum(flow) - (t_board - T_AMBIENT_C) / R_BOARD_AMB)
    return jnp.concatenate([t_node, t_board[None]])


def transient_trace(power_trace_w: jnp.ndarray, dt_s,
                    init: jnp.ndarray | None = None) -> jnp.ndarray:
    """Integrate a (K, 3) power trace from ``init`` (default ambient).

    Returns (K, 4) temperatures — the ``lax.scan`` twin of
    ``repro.core.thermal.simulate_trace``.
    """
    t0 = (jnp.full((4,), T_AMBIENT_C) if init is None
          else jnp.asarray(init, jnp.float32))
    dt = jnp.float32(dt_s)

    def step(temps, p):
        nxt = euler_step(temps, p, dt)
        return nxt, nxt

    _, out = jax.lax.scan(step, t0, jnp.asarray(power_trace_w, jnp.float32))
    return out


def binned_power_trace(start_us: jnp.ndarray, finish_us: jnp.ndarray,
                       onpe: jnp.ndarray, valid: jnp.ndarray,
                       node_of_pe: jnp.ndarray, power_active: jnp.ndarray,
                       power_idle: jnp.ndarray, makespan_us: jnp.ndarray,
                       bins: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-node power trace of one realised schedule.

    Args (one simulation): start/finish/valid (J, T); onpe (J, T) i32;
    node_of_pe (P,) i32; power_active/power_idle (P,).
    Returns ((bins, 3) node power in W, scalar bin width in seconds).
    """
    P = power_active.shape[0]
    dt_us = jnp.maximum(makespan_us, 1e-6) / bins
    edges = jnp.arange(bins, dtype=jnp.float32) * dt_us            # (K,)
    s = jnp.where(valid, start_us, 0.0)[..., None]                 # (J,T,1)
    f = jnp.where(valid, finish_us, 0.0)[..., None]
    overlap = (jnp.minimum(f, edges + dt_us)
               - jnp.maximum(s, edges))                            # (J,T,K)
    overlap = jnp.clip(overlap, 0.0, dt_us)
    # masked fixed-order sums, not one-hot contractions (DESIGN.md §1)
    hit = ((onpe[..., None] == jnp.arange(P, dtype=onpe.dtype))
           & valid[..., None])                                     # (J,T,P)
    terms = jnp.where(hit[:, :, None, :], overlap[..., None], 0.0)
    busy = fixed_order_sum(terms.reshape((-1,) + terms.shape[2:]))  # (K,P)
    util = jnp.clip(busy / dt_us, 0.0, 1.0)
    power_pe = power_active * util + power_idle * (1.0 - util)     # (K,P)
    on_node = node_of_pe[:, None] == jnp.arange(_ref.NUM_NODES)    # (P,3)
    node_p = fixed_order_sum(
        jnp.where(on_node, power_pe[..., None], 0.0).swapaxes(0, 1))  # (K,3)
    return node_p, dt_us * 1e-6


def peak_temperature(power_trace_w: jnp.ndarray, dt_s: jnp.ndarray,
                     repeats: int = 3) -> jnp.ndarray:
    """Peak on-chip temperature under a sustained periodic (K, 3) trace.

    Power is constant within a bin, so each bin advances by the *exact*
    linear-RC solution  x' = e^{M·dt} x + M⁻¹(e^{M·dt} − I) u  — e^{M·dt}
    built per trace from the host-precomputed spectral form (DESIGN.md §6;
    batch-width-independent rounding, unlike a batched ``expm``),
    unconditionally stable for any bin width (unlike forward Euler, which
    diverges once dt exceeds ~2·min(RC); bins are makespan/K and the
    makespan is workload-dependent, so no dt bound can be assumed here).
    """
    power_trace_w = jnp.asarray(power_trace_w, jnp.float32)
    Am1, B = _ref.exact_step_matrices_jax(dt_s)
    K = power_trace_w.shape[0]
    t0 = steady_state(fixed_order_sum(power_trace_w) / K)
    idx = jnp.arange(K * repeats, dtype=jnp.int32) % K

    def step(temps, k):
        nxt = _ref.exact_step_jax(temps, power_trace_w[k], Am1, B)
        return nxt, jnp.max(nxt[:3])

    _, peaks = jax.lax.scan(step, t0, idx)
    return jnp.maximum(jnp.max(peaks), jnp.max(t0[:3]))


@functools.partial(jax.jit, static_argnames=("bins", "repeats"))
def peak_temperature_grid(sim_out: Dict, node_of_pe: jnp.ndarray,
                          power_active: jnp.ndarray, power_idle: jnp.ndarray,
                          bins: int = 32, repeats: int = 3) -> jnp.ndarray:
    """(D, S) peak temperatures from batched simulation output.

    ``sim_out`` is the static kernel's output dict with leading (D, S)
    axes, as the sweep's grid program (``scenario.shardexec._sweep_grid``)
    produces it; ``node_of_pe``/``power_active``/``power_idle`` are (D, P).
    """
    def one(start, finish, onpe, scheduled, makespan, nodes, p_act, p_idle):
        trace, dt = binned_power_trace(start, finish, onpe, scheduled,
                                       nodes, p_act, p_idle, makespan, bins)
        return peak_temperature(trace, dt, repeats=repeats)

    per_trace = jax.vmap(one, in_axes=(0, 0, 0, 0, 0, None, None, None))
    per_design = jax.vmap(per_trace, in_axes=(0, 0, 0, 0, 0, 0, 0, 0))
    return per_design(sim_out["start"], sim_out["finish"], sim_out["onpe"],
                      sim_out["scheduled"], sim_out["makespan_us"],
                      node_of_pe, power_active, power_idle)
