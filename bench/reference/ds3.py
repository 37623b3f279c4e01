"""Plain reference of the DS3 simulation semantics, independent of the
program under test.

A copy of the event-heap oracle (DS3's discrete-event kernel) with the
tables it needs: the PE types, DVFS operating points and power model, the
task profiles of paper Table 1, the five reference application DAGs, the
MET/ETF/ILP-table schedulers, the performance/userspace/ondemand/throttle
governors and the lumped RC thermal network.  It imports nothing of the
program and takes nothing the program made; a benchmark run gives it the
same design fields, application names, traces and policies it gave the
program, and compares the per-lane statistics.

Semantics (DS3, arXiv:1908.03664 §2): a task reaches its decision epoch at
``max(arrival, max_p finish_p)``; the scheduler picks a PE; the task joins
that PE's FIFO queue (``start = max(data ready on the PE incl. comm,
PE free)``).  Epochs are ordered by (ready, job, task).  CPU latency scales
with the cluster's DVFS frequency, latched at task start.  Dynamic governors
update cluster frequencies at sampling-window boundaries from measured
utilisation; the RC network integrates each window's realised power, and
the throttle clamps clusters hotter than its cap to the lowest OPP.

Precision.  Schedule times are computed in float32, as the configuration
states; energy and temperature in float64.  ``time_dtype`` and
``value_dtype`` lower both for the control (``ml_dtypes.bfloat16``): the
same reference one precision step below what the configuration states.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import heapq
import math
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

INF = math.inf

# ---------------------------------------------------------------- resources
CPU_BIG, CPU_LITTLE = "A15", "A7"
ACC_SCRAMBLER, ACC_FFT, ACC_VITERBI = "SCR_ACC", "FFT_ACC", "VIT_ACC"
CPU_TYPES = (CPU_BIG, CPU_LITTLE)

# Odroid-XU3 operating points (GHz, V) per CPU cluster
OPP_TABLE = {
    CPU_BIG: [(0.6, 0.90), (1.0, 1.00), (1.4, 1.1), (1.8, 1.2), (2.0, 1.25)],
    CPU_LITTLE: [(0.6, 0.95), (0.8, 1.00), (1.0, 1.05), (1.2, 1.15),
                 (1.4, 1.25)],
}
NOMINAL_FREQ = {CPU_BIG: 2.0, CPU_LITTLE: 1.4}
MAX_OPP_LEVELS = max(len(v) for v in OPP_TABLE.values())
POWER_COEFF = {
    CPU_BIG: dict(ceff=0.45, leak=0.25),
    CPU_LITTLE: dict(ceff=0.10, leak=0.03),
    ACC_SCRAMBLER: dict(ceff=0.02, leak=0.01),
    ACC_FFT: dict(ceff=0.05, leak=0.02),
    ACC_VITERBI: dict(ceff=0.05, leak=0.02),
}
ACC_POWER_ACTIVE = {ACC_SCRAMBLER: 0.15, ACC_FFT: 0.35, ACC_VITERBI: 0.30}

# task latency (us) per PE type: paper Table 1 (WiFi-TX) and the DS3 suite
PROFILES: Dict[str, Dict[str, float]] = {
    "scrambler_encoder": {ACC_SCRAMBLER: 8, CPU_LITTLE: 22, CPU_BIG: 10},
    "interleaver":       {CPU_LITTLE: 10, CPU_BIG: 4},
    "qpsk_modulation":   {CPU_LITTLE: 15, CPU_BIG: 8},
    "pilot_insertion":   {CPU_LITTLE: 5,  CPU_BIG: 3},
    "inverse_fft":       {ACC_FFT: 16, CPU_LITTLE: 296, CPU_BIG: 118},
    "crc":               {CPU_LITTLE: 5,  CPU_BIG: 3},
    "match_filter":      {CPU_LITTLE: 28, CPU_BIG: 12},
    "payload_extract":   {CPU_LITTLE: 8,  CPU_BIG: 4},
    "fft":               {ACC_FFT: 16, CPU_LITTLE: 296, CPU_BIG: 118},
    "pilot_extract":     {CPU_LITTLE: 6,  CPU_BIG: 3},
    "qpsk_demodulation": {CPU_LITTLE: 18, CPU_BIG: 9},
    "deinterleaver":     {CPU_LITTLE: 12, CPU_BIG: 5},
    "viterbi_decoder":   {ACC_VITERBI: 20, CPU_LITTLE: 520, CPU_BIG: 190},
    "sc_modulation":     {CPU_LITTLE: 10, CPU_BIG: 5},
    "sc_demodulation":   {CPU_LITTLE: 12, CPU_BIG: 6},
    "rrc_filter":        {CPU_LITTLE: 45, CPU_BIG: 18},
    "sync":              {CPU_LITTLE: 30, CPU_BIG: 12},
    "lfm_gen":           {CPU_LITTLE: 14, CPU_BIG: 6},
    "conj_multiply":     {CPU_LITTLE: 24, CPU_BIG: 10},
    "amplitude":         {CPU_LITTLE: 12, CPU_BIG: 5},
    "peak_detect":       {CPU_LITTLE: 8,  CPU_BIG: 4},
    "pd_stack":          {CPU_LITTLE: 10, CPU_BIG: 4},
    "doppler_fft":       {ACC_FFT: 16, CPU_LITTLE: 296, CPU_BIG: 118},
    "cfar":              {CPU_LITTLE: 40, CPU_BIG: 16},
}


@dataclasses.dataclass(frozen=True)
class PE:
    pe_id: int
    pe_type: str
    cluster: int

    @property
    def is_cpu(self) -> bool:
        return self.pe_type in CPU_TYPES


@dataclasses.dataclass(frozen=True)
class Comm:
    """latency = 0 on the same PE, else startup + bytes/bw, times the
    penalty across clusters."""
    startup_us: float = 0.5
    bw_bytes_per_us: float = 8_000.0
    cross_cluster_penalty: float = 2.0

    def latency(self, nbytes: float, src: PE, dst: PE) -> float:
        if src.pe_id == dst.pe_id:
            return 0.0
        t = self.startup_us + nbytes / self.bw_bytes_per_us
        if src.cluster != dst.cluster:
            t *= self.cross_cluster_penalty
        return t


@dataclasses.dataclass
class SoC:
    pes: List[PE]
    comm: Comm

    @property
    def num_pes(self) -> int:
        return len(self.pes)

    def base_latency(self, task: str, pe: PE) -> float:
        return PROFILES.get(task, {}).get(pe.pe_type, INF)


def make_soc(num_big=4, num_little=4, num_scr=2, num_fft=4, num_vit=0,
             cross_cluster_penalty=2.0) -> SoC:
    """PEs in cluster order: big (0), LITTLE (1), then the accelerator
    fabric (2): scramblers, FFTs, Viterbis."""
    kinds = ([(CPU_BIG, 0)] * num_big + [(CPU_LITTLE, 1)] * num_little
             + [(ACC_SCRAMBLER, 2)] * num_scr + [(ACC_FFT, 2)] * num_fft
             + [(ACC_VITERBI, 2)] * num_vit)
    return SoC([PE(i, t, c) for i, (t, c) in enumerate(kinds)],
               Comm(cross_cluster_penalty=float(cross_cluster_penalty)))


# ------------------------------------------------------------- applications
@dataclasses.dataclass(frozen=True)
class Task:
    name: str
    task_id: int
    predecessors: Tuple[int, ...]
    out_bytes: float = 1024.0


@dataclasses.dataclass(frozen=True)
class App:
    name: str
    tasks: Tuple[Task, ...]

    @property
    def num_tasks(self) -> int:
        return len(self.tasks)


def _chain(name, names, out_bytes=1024.0) -> App:
    return App(name, tuple(Task(n, i, (i - 1,) if i else (), out_bytes)
                           for i, n in enumerate(names)))


def _pulse_doppler() -> App:
    nfft = 4
    tasks = [Task("pd_stack", 0, (), 4096)]
    tasks += [Task("fft", 1 + i, (0,), 4096) for i in range(nfft)]
    join = 1 + nfft
    tasks += [Task("doppler_fft", join, tuple(range(1, join)), 4096),
              Task("amplitude", join + 1, (join,), 2048),
              Task("cfar", join + 2, (join + 1,), 1024)]
    return App("pulse_doppler", tuple(tasks))


APPS = {
    "wifi_tx": lambda: _chain("wifi_tx", [
        "scrambler_encoder", "interleaver", "qpsk_modulation",
        "pilot_insertion", "inverse_fft", "crc"]),
    "wifi_rx": lambda: App("wifi_rx", (
        Task("match_filter", 0, (), 2048),
        Task("payload_extract", 1, (0,), 2048),
        Task("fft", 2, (1,), 2048),
        Task("pilot_extract", 3, (2,), 512),
        Task("qpsk_demodulation", 4, (2, 3), 1024),
        Task("deinterleaver", 5, (4,), 1024),
        Task("viterbi_decoder", 6, (5,), 1024))),
    "single_carrier": lambda: App("single_carrier", (
        Task("scrambler_encoder", 0, (), 512),
        Task("sc_modulation", 1, (0,), 512),
        Task("rrc_filter", 2, (1,), 1024),
        Task("sync", 3, (2,), 1024),
        Task("sc_demodulation", 4, (3,), 512),
        Task("crc", 5, (4,), 256))),
    "range_detection": lambda: App("range_detection", (
        Task("lfm_gen", 0, (), 4096),
        Task("fft", 1, (0,), 4096),
        Task("fft", 2, (0,), 4096),
        Task("conj_multiply", 3, (1, 2), 4096),
        Task("inverse_fft", 4, (3,), 4096),
        Task("amplitude", 5, (4,), 2048),
        Task("peak_detect", 6, (5,), 64))),
    "pulse_doppler": _pulse_doppler,
}


def app(name: str) -> App:
    return APPS[name]()


# -------------------------------------------------------------------- power
def opp_voltage(pe_type: str, freq_ghz: float) -> float:
    table = OPP_TABLE[pe_type]
    i = bisect.bisect_left([f for f, _ in table], freq_ghz - 1e-9)
    return table[min(i, len(table) - 1)][1]


def active_power(pe: PE, freq_ghz: float) -> float:
    if pe.is_cpu:
        v = opp_voltage(pe.pe_type, freq_ghz)
        c = POWER_COEFF[pe.pe_type]
        return c["ceff"] * v * v * freq_ghz + c["leak"]
    return ACC_POWER_ACTIVE[pe.pe_type] + POWER_COEFF[pe.pe_type]["leak"]


def idle_power(pe: PE) -> float:
    return POWER_COEFF[pe.pe_type]["leak"]


# ---------------------------------------------------------------- governors
def capped_levels(pe_type: str, caps: Optional[Mapping[str, float]]):
    opps = [f for f, _ in OPP_TABLE[pe_type]]
    if caps is not None and pe_type in caps:
        opps = [f for f in opps if f <= caps[pe_type] + 1e-9] or opps[:1]
    return opps


@dataclasses.dataclass
class Governor:
    """``kind``: "performance" (top OPP), "caps" (userspace at ``caps``),
    "ondemand" or "throttle" (dynamic; ladders capped at ``caps``)."""
    kind: str = "performance"
    caps: Optional[Dict[str, float]] = None
    up_threshold: float = 0.80
    sample_window_us: float = 50.0
    thermal_cap_c: float = INF
    thermal_dt_s: Optional[float] = None

    @property
    def dynamic(self) -> bool:
        return self.kind in ("ondemand", "throttle")

    def initial_freq(self, pe_type: str) -> float:
        if self.kind == "performance":
            return OPP_TABLE[pe_type][-1][0]
        if self.kind == "caps":
            return self.caps[pe_type]
        return capped_levels(pe_type, self.caps)[0]

    def update(self, pe_type: str, utilization: float) -> float:
        """Ondemand: above the threshold jump to f_max, else the smallest
        level covering f_max * util / threshold."""
        opps = capped_levels(pe_type, self.caps)
        fmax = opps[-1]
        if utilization > self.up_threshold:
            return fmax
        target = fmax * max(utilization, 0.0) / self.up_threshold
        row = opps + [opps[-1]] * (MAX_OPP_LEVELS - len(opps))
        return opps[next(i for i, f in enumerate(row)
                         if f >= target - 1e-9)]


def make_governor(name: str, params: Mapping[str, float] = (),
                  caps: Optional[Dict[str, float]] = None) -> Governor:
    """The reference twin of a configuration's governor entry."""
    params = dict(params)
    if name == "performance":
        return Governor("performance")
    if name == "design":
        return Governor("caps", caps=dict(caps))
    if name == "ondemand":
        g = Governor("ondemand", caps=caps, **params)
        if g.thermal_dt_s is None:
            g.thermal_dt_s = g.sample_window_us * 1e-6
        return g
    if name == "throttle":
        params.setdefault("thermal_cap_c", 60.0)
        params.setdefault("thermal_dt_s", 0.05)
        return Governor("throttle", caps=caps, **params)
    raise ValueError(f"unknown governor {name!r}")


# ------------------------------------------------------------------ thermal
T_AMBIENT_C = 25.0
NUM_NODES = 3
R_TO_BOARD = np.array([2.0, 4.0, 3.0])
C_NODE = np.array([0.15, 0.05, 0.10])
R_BOARD_AMB = 1.5
C_BOARD = 20.0


def cluster_nodes(soc: SoC) -> np.ndarray:
    return np.asarray([0 if p.pe_type == CPU_BIG else
                       1 if p.pe_type == CPU_LITTLE else 2
                       for p in soc.pes], np.int64)


def rc_state_matrix() -> np.ndarray:
    a = 1.0 / (R_TO_BOARD * C_NODE)
    top = np.concatenate([np.diag(-a), a[:, None]], axis=1)
    b_in = 1.0 / (R_TO_BOARD * C_BOARD)
    b_out = -(np.sum(1.0 / R_TO_BOARD) + 1.0 / R_BOARD_AMB) / C_BOARD
    return np.concatenate([top, np.concatenate([b_in, [b_out]])[None]])


def exact_step_matrices(dt_s: float):
    """x' = A x + B u with A = e^{M dt}, B = M^-1 (A - I)."""
    import scipy.linalg
    M = rc_state_matrix()
    A = scipy.linalg.expm(M * float(dt_s))
    return A, np.linalg.solve(M, A - np.eye(4))


def exact_step(temps, power_w, A, B):
    u = np.concatenate([np.asarray(power_w, np.float64) / C_NODE,
                        [T_AMBIENT_C / (R_BOARD_AMB * C_BOARD)]])
    return A @ np.asarray(temps, np.float64) + B @ u


def steady_state(power_w: np.ndarray) -> np.ndarray:
    tb = T_AMBIENT_C + R_BOARD_AMB * float(np.sum(power_w))
    return np.concatenate([tb + R_TO_BOARD * power_w, [tb]])


# --------------------------------------------------------------- schedulers
def solve_optimal_table(soc: SoC, a: App,
                        max_states: int = 2_000_000) -> Dict[int, int]:
    """Minimum-makespan PE per task of ONE job instance (branch and bound
    in topological order, identical PEs symmetry-broken); ties go to the
    least maximum per-PE load."""
    T, n = a.num_tasks, soc.num_pes
    ex = np.asarray([[soc.base_latency(t.name, pe) for pe in soc.pes]
                     for t in a.tasks], np.float32)
    best = {"key": (INF, INF), "assign": None}
    states = [0]

    def rec(i, assign, finish, pe_free, pe_load):
        states[0] += 1
        if states[0] > max_states:
            return
        cur = (max(finish) if finish else 0.0, max(pe_load) if assign else 0.0)
        if cur >= best["key"]:
            return
        if i == T:
            best["key"], best["assign"] = cur, list(assign)
            return
        seen = set()
        for j in (int(j) for j in np.argsort(ex[i])):
            if not np.isfinite(ex[i, j]):
                continue
            key = (soc.pes[j].pe_type, pe_free[j], pe_load[j])
            if key in seen:
                continue
            seen.add(key)
            ready = 0.0
            for p in a.tasks[i].predecessors:
                ready = max(ready, finish[p] + soc.comm.latency(
                    float(np.float32(a.tasks[p].out_bytes)),
                    soc.pes[assign[p]], soc.pes[j]))
            f = max(ready, pe_free[j]) + float(ex[i, j])
            old_free, old_load = pe_free[j], pe_load[j]
            assign.append(j)
            finish.append(f)
            pe_free[j], pe_load[j] = f, old_load + float(ex[i, j])
            rec(i + 1, assign, finish, pe_free, pe_load)
            assign.pop()
            finish.pop()
            pe_free[j], pe_load[j] = old_free, old_load

    rec(0, [], [], [0.0] * n, [0.0] * n)
    if best["assign"] is None:
        raise RuntimeError(f"no table schedule found for {a.name}")
    return dict(enumerate(best["assign"]))


@functools.lru_cache(maxsize=256)
def _table(kinds: Tuple[Tuple[str, int], ...], comm: Comm,
           app_name: str) -> Dict[int, int]:
    soc = SoC([PE(i, t, c) for i, (t, c) in enumerate(kinds)], comm)
    return solve_optimal_table(soc, app(app_name))


# -------------------------------------------------------------------- kernel
@dataclasses.dataclass
class Record:
    job_id: int
    task_id: int
    pe_id: int
    start_us: float
    finish_us: float
    freq_ghz: float


@dataclasses.dataclass
class LaneResult:
    avg_latency_us: float
    makespan_us: float
    energy_j: float
    peak_temp_c: float


def simulate(soc: SoC, apps: Sequence[App], arrival_us: np.ndarray,
             app_index: np.ndarray, scheduler: str, governor: Governor,
             bins: int = 32, repeats: int = 3,
             time_dtype=np.float32, value_dtype=np.float64) -> LaneResult:
    """One lane: the event-heap simulation, its energy and peak
    temperature.  ``bins``/``repeats`` shape the static governors' binned
    RC peak; dynamic governors report the peak of the in-loop RC state,
    drained to the makespan."""
    F = time_dtype
    V = value_dtype
    n = soc.num_pes
    pe_free = [F(0.0)] * n
    clusters = sorted({p.cluster for p in soc.pes if p.is_cpu})
    cl_type = {c: next(p.pe_type for p in soc.pes
                       if p.cluster == c and p.is_cpu) for c in clusters}
    cl_pes = {c: [p.pe_id for p in soc.pes if p.cluster == c and p.is_cpu]
              for c in clusters}
    freq = {c: governor.initial_freq(cl_type[c]) for c in clusters}
    nodes = cluster_nodes(soc)
    table = ({a.name: _table(tuple((p.pe_type, p.cluster) for p in soc.pes),
                             soc.comm, a.name) for a in apps}
             if scheduler == "table" else None)

    dynamic = governor.dynamic
    throttle = dynamic and math.isfinite(governor.thermal_cap_c)
    window_us = governor.sample_window_us if dynamic else None
    next_end = window_us if dynamic else INF
    committed: List[Record] = []
    temps = np.full(4, T_AMBIENT_C)
    peak = T_AMBIENT_C
    if dynamic:
        rc_a, rc_b = exact_step_matrices(governor.thermal_dt_s)
        cl_node = {c: int(nodes[cl_pes[c][0]]) for c in clusters}
        cl_opps = {c: capped_levels(cl_type[c], governor.caps)
                   for c in clusters}

    def advance_windows(now: float) -> None:
        nonlocal next_end, temps, peak
        while dynamic and next_end <= now:
            w0, w1 = next_end - window_us, next_end
            width = w1 - w0
            new = {}
            for c in clusters:
                busy = sum(max(0.0, min(r.finish_us, w1) - max(r.start_us, w0))
                           for r in committed if r.pe_id in cl_pes[c])
                new[c] = governor.update(
                    cl_type[c], busy / max(width * len(cl_pes[c]), 1e-9))
            p = np.zeros(NUM_NODES)
            busy_pe = np.zeros(n)
            for r in committed:
                ov = max(0.0, min(r.finish_us, w1) - max(r.start_us, w0))
                if ov > 0.0:
                    p[nodes[r.pe_id]] += (active_power(soc.pes[r.pe_id],
                                                       r.freq_ghz) * ov / width)
                    busy_pe[r.pe_id] += ov
            for j, pe in enumerate(soc.pes):
                p[nodes[j]] += idle_power(pe) * (
                    1.0 - min(max(busy_pe[j] / width, 0.0), 1.0))
            temps = np.asarray(exact_step(temps, p, rc_a, rc_b)
                               .astype(V), np.float64)
            peak = max(peak, float(temps[:3].max()))
            if throttle:
                for c in clusters:
                    opps = cl_opps[c]
                    cur = min(range(len(opps)),
                              key=lambda i, f=new[c]: abs(opps[i] - f))
                    if temps[cl_node[c]] > governor.thermal_cap_c:
                        cur = 0
                    new[c] = opps[cur]
            freq.update(new)
            committed[:] = [r for r in committed if r.finish_us > w1]
            next_end += window_us

    job_apps = [apps[int(i)] for i in app_index]
    finish: Dict[Tuple[int, int], float] = {}
    on_pe: Dict[Tuple[int, int], int] = {}
    n_done: Dict[Tuple[int, int], int] = {}
    heap: List[Tuple[float, int, int]] = []
    for jid, a in enumerate(job_apps):
        for t in a.tasks:
            n_done[(jid, t.task_id)] = 0
            if not t.predecessors:
                heapq.heappush(heap, (float(arrival_us[jid]), jid, t.task_id))

    records: List[Record] = []
    while heap:
        ready, jid, tid = heapq.heappop(heap)
        advance_windows(ready)
        a = job_apps[jid]
        task = a.tasks[tid]
        fs = [F(NOMINAL_FREQ[p.pe_type] / freq[p.cluster]) if p.is_cpu
              else F(1.0) for p in soc.pes]
        preds = task.predecessors
        pf = [F(finish[(jid, p)]) for p in preds]
        pp = [on_pe[(jid, p)] for p in preds]
        pb = [float(np.float32(a.tasks[p].out_bytes)) for p in preds]

        def exec_on(j):
            base = soc.base_latency(task.name, soc.pes[j])
            return F(base * fs[j]) if soc.pes[j].is_cpu else F(base)

        if scheduler == "table":
            pe_id = int(table[a.name][tid])
        elif scheduler == "met":
            ex = [exec_on(j) for j in range(n)]
            pe_id = int(np.argmin(np.asarray(ex, np.float64)))
        elif scheduler == "etf":
            fin = []
            for j, pe in enumerate(soc.pes):
                r = F(ready)
                for k in range(len(preds)):
                    c = soc.comm.latency(pb[k], soc.pes[pp[k]], pe)
                    r = F(max(float(r), float(pf[k]) + c))
                fin.append(F(max(r, F(pe_free[j])) + exec_on(j)))
            pe_id = int(np.argmin(np.asarray(fin, np.float64)))
        else:
            raise ValueError(f"unknown scheduler {scheduler!r}")
        pe = soc.pes[pe_id]
        data_ready = F(ready)
        for k in range(len(preds)):
            c = soc.comm.latency(pb[k], soc.pes[pp[k]], pe)
            data_ready = max(data_ready, F(pf[k] + F(c)))
        ex_us = exec_on(pe_id)
        if not np.isfinite(float(ex_us)):
            raise RuntimeError(f"{scheduler} picked unsupported PE {pe_id} "
                               f"for {task.name}")
        start = max(F(data_ready), pe_free[pe_id])
        fin_t = F(start + ex_us)
        pe_free[pe_id] = fin_t
        rec = Record(jid, tid, pe_id, float(start), float(fin_t),
                     float(freq[pe.cluster]) if pe.is_cpu else 0.0)
        records.append(rec)
        committed.append(rec)
        finish[(jid, tid)] = float(fin_t)
        on_pe[(jid, tid)] = pe_id
        for child in a.tasks:
            if tid in child.predecessors:
                key = (jid, child.task_id)
                n_done[key] += 1
                if n_done[key] == len(child.predecessors):
                    heapq.heappush(heap, (
                        max(float(arrival_us[jid]),
                            max(finish[(jid, p)]
                                for p in child.predecessors)),
                        jid, child.task_id))

    job_finish = np.zeros(len(job_apps), np.float32)
    for r in records:
        job_finish[r.job_id] = max(job_finish[r.job_id], r.finish_us)
    makespan = float(max((r.finish_us for r in records), default=0.0))
    if dynamic:
        while next_end - window_us < makespan:
            advance_windows(next_end)

    # energy: active at the latched frequency, idle leakage elsewhere
    e = V(0.0)
    for pe in soc.pes:
        busy = V(0.0)
        e_pe = V(0.0)
        for r in records:
            if r.pe_id == pe.pe_id:
                dt = V(max(0.0, r.finish_us - r.start_us))
                busy = V(busy + dt)
                e_pe = V(e_pe + V(active_power(pe, r.freq_ghz)) * dt)
        e_pe = V(e_pe + V(idle_power(pe)) * V(max(0.0, makespan
                                                   - float(busy))))
        e = V(e + e_pe)
    lat = np.asarray(job_finish, np.float64) - np.asarray(arrival_us,
                                                          np.float64)
    avg_lat = float(V(np.mean(lat)))
    if not dynamic:
        peak = binned_peak(soc, records, governor, makespan, bins, repeats,
                           value_dtype=V)
    return LaneResult(avg_lat, makespan, float(e) * 1e-6, peak)


def binned_peak(soc: SoC, records: Sequence[Record], governor: Governor,
                makespan_us: float, bins: int, repeats: int,
                value_dtype=np.float64) -> float:
    """Peak RC temperature of a static-governor schedule: per-PE busy
    fraction in ``bins`` time bins, node power, the periodic steady state
    of the mean power, then ``bins * repeats`` exact steps."""
    dt_us = max(makespan_us, 1e-6) / bins
    edges = np.arange(bins) * dt_us
    busy = np.zeros((bins, soc.num_pes))
    for r in records:
        busy[:, r.pe_id] += np.clip(np.minimum(r.finish_us, edges + dt_us)
                                    - np.maximum(r.start_us, edges), 0, dt_us)
    util = np.clip(busy / dt_us, 0.0, 1.0)
    p_act = np.asarray([active_power(pe, governor.initial_freq(pe.pe_type)
                                     if pe.is_cpu else 0.0)
                        for pe in soc.pes])
    p_idle = np.asarray([idle_power(pe) for pe in soc.pes])
    power_pe = p_act * util + p_idle * (1.0 - util)
    nodes = cluster_nodes(soc)
    node_p = np.stack([power_pe[:, nodes == k].sum(axis=1)
                       for k in range(NUM_NODES)], axis=1)
    A, B = exact_step_matrices(dt_us * 1e-6)
    temps = steady_state(node_p.mean(axis=0)).astype(value_dtype)
    peak = float(temps[:3].max())
    for k in range(bins * repeats):
        temps = np.asarray(exact_step(temps, node_p[k % bins], A, B)
                           .astype(value_dtype), np.float64)
        peak = max(peak, float(temps[:3].max()))
    return peak


def tasks_of(apps: Sequence[App], app_index: np.ndarray) -> int:
    """DAG tasks a trace asks for: the sum over its jobs of the job's
    application task count."""
    counts = np.asarray([a.num_tasks for a in apps])
    return int(counts[np.asarray(app_index, np.int64)].sum())

