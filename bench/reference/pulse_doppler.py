"""The plain reference's pulse-Doppler CPI DAG, for ``ds3.simulate``.

One coherent processing interval (CPI) of pulse-Doppler radar, as the
configuration ``ds3_table2_pulse_doppler`` states it: ``pd_stack``; per
pulse a pulse-compression chain ``fft → conj_multiply → inverse_fft``; then
the corner turn, ``doppler_fft`` per Doppler bin, each fed by every
pulse's ``inverse_fft``.  Tasks are numbered stage by stage; every edge
carries 4,096 bytes.  At 128 pulses and 64 bins it has the 449 tasks DS3
(arXiv:2003.09016) lists for its pulse-Doppler application.  Imports
nothing of the program.
"""
from __future__ import annotations

from typing import List

from .ds3 import App, Task

EDGE_BYTES = 4096


def app(pulses: int = 128, doppler_bins: int = 64) -> App:
    P, B = pulses, doppler_bins
    fft, conj, ifft = 1, 1 + P, 1 + 2 * P
    tasks: List[Task] = [Task("pd_stack", 0, (), EDGE_BYTES)]
    tasks += [Task("fft", fft + p, (0,), EDGE_BYTES) for p in range(P)]
    tasks += [Task("conj_multiply", conj + p, (fft + p,), EDGE_BYTES)
              for p in range(P)]
    tasks += [Task("inverse_fft", ifft + p, (conj + p,), EDGE_BYTES)
              for p in range(P)]
    corner = tuple(range(ifft, ifft + P))
    tasks += [Task("doppler_fft", ifft + P + b, corner, EDGE_BYTES)
              for b in range(B)]
    name = ("pulse_doppler_cpi" if (pulses, doppler_bins) == (128, 64)
            else f"pulse_doppler_cpi_{pulses}x{doppler_bins}")
    return App(name, tuple(tasks))
