"""Model step: mean ``PagedLM.decode_step`` span, blocked on its logits,
in ms."""
from instrument import DECODE
from readers import mean


def read(run):
    v = mean(run.spans.durations(DECODE))
    return None if v is None else 1e3 * v
