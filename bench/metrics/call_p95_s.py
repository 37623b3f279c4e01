"""95th percentile of the wall time of every call in the window (end to
end): from the call until its results are on the host.  Linear
interpolation between order statistics (``numpy.percentile``).  Reported
by cells whose window holds at least 200 calls, so that ten or more lie
beyond it."""
import numpy as np

UNIT = "s"
SOURCE = "host_clock"
BETTER = "lower"


def read(w):
    return float(np.percentile(np.asarray(w.call_s), 95))
