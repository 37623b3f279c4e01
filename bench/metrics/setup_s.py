"""Set-up time: process start to the first timed call (import, TPU
initialisation, the cell's inputs, compile-cache load or compilation, and
the warm-up call of every program the window uses)."""

UNIT = "s"
SOURCE = "host_clock"
BETTER = "lower"


def read(w):
    return w.setup_s
