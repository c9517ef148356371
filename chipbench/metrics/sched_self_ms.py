"""Scheduler self time: mean ``ServeEngine.step`` span minus the spans
directly inside it (decode step, prefill, restore), in ms."""
from instrument import STEP
from readers import mean


def read(run):
    v = mean(run.spans.self_times(STEP))
    return None if v is None else 1e3 * v
