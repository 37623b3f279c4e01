"""Simulated DAG tasks per second of the window (end to end).

All tasks simulated by the window's calls, counted from the traces the
benchmark generated (the sum over lanes and jobs of the job's application
task count), divided by the window's length on the host clock.  What a
sweep user waits on: the ROADMAP's "simulated tasks retired per second"."""
import numpy as np

UNIT = "tasks/s"
SOURCE = "host_clock"
BETTER = "higher"


def read(w):
    return float(np.sum(w.tasks)) / w.window_s
