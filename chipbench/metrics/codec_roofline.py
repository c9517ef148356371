"""Kernels: the fused transit codec's least time on the chip for the
pages the window moved (each page is one call per layer for K and one
for V, ``costs/codec.py``) over its device time in the trace, in %."""
from readers import roofline


def read(run):
    if run.trace is None:
        return None
    cost = run.cost("codec")
    calls = 2 * run.geometry["L"]
    o_out, b_out = cost.page_out(run.geometry)
    o_in, b_in = cost.page_in(run.geometry)
    n_out, n_in = run.counters["pages_out"], run.counters["pages_in"]
    if not (n_out or n_in):
        return None
    ops = calls * (n_out * o_out + n_in * o_in)
    nbytes = calls * (n_out * b_out + n_in * b_in)
    return roofline(ops, nbytes, run.trace.kernel_s(cost.NAMES), run.peaks)
