"""JX003 fixtures — host profiler spans: inside a jitted body (bad), around
the jitted call on the host (clean)."""
import jax
from jax.profiler import TraceAnnotation

from repro.obs.metrics import span


@jax.jit
def spanned(x):
    with span("repro.launch"):         # line 11: JX003 span
        return x + 1


@jax.jit
def annotated(x):
    with jax.profiler.TraceAnnotation("step"):     # line 17: JX003
        return x * 2


@jax.jit
def imported(x):
    with TraceAnnotation("step"):      # line 23: JX003
        return x - 1


def host_call(x):
    with span("repro.launch"):         # host code: not jit-reachable
        return spanned(x)
