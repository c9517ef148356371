"""Kernels: paged decode attention's least time on the chip (valid
tokens' pages and operations, ``costs/paged_attention.py``) over its
device time in the trace, in %."""
from readers import roofline


def read(run):
    if run.trace is None or not run.spans.decode_calls:
        return None
    cost = run.cost("paged_attention")
    ops = nbytes = 0.0
    for _, lens in run.spans.decode_calls:
        o, b = cost.work(run.geometry, run.dims, lens)
        ops += o * run.geometry["L"]
        nbytes += b * run.geometry["L"]
    return roofline(ops, nbytes, run.trace.kernel_s(cost.NAMES), run.peaks)
