"""Whole resume step: model operations of the steps that restored a
session (restore plus one decode step) over those steps' span time
times the chip's bf16 peak, in %.  It bounds what the codec's roofline
can claim for ``resume_p95_ms``."""
from instrument import ACTIVATE, STEP
from readers import step_flops


def read(run):
    spans = run.spans.spans
    steps = [(t0, t1) for n, t0, t1, _ in spans if n == STEP
             and any(m == ACTIVATE and t0 <= a0 and a1 <= t1
                     for m, a0, a1, _ in spans)]
    if not steps:
        return None
    ops = sum(step_flops(run.dims, lens)
              for tc, lens in run.spans.decode_calls
              if any(t0 <= tc <= t1 for t0, t1 in steps))
    t = sum(t1 - t0 for t0, t1 in steps)
    return 100.0 * ops / (t * run.peaks["bf16_flops"]) if ops else None
