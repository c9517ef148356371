"""KV cache manager, restore: mean ``PagedKVCache.activate`` span, in
ms."""
from instrument import ACTIVATE
from readers import mean


def read(run):
    v = mean(run.spans.durations(ACTIVATE))
    return None if v is None else 1e3 * v
