"""KV cache manager, page-out and spill: mean ``ServeEngine.suspend``
span, in ms."""
from instrument import SUSPEND
from readers import mean


def read(run):
    v = mean(run.spans.durations(SUSPEND))
    return None if v is None else 1e3 * v
