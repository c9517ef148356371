"""Model step: the decode steps' model operations over their summed
span time times the chip's bf16 peak, in %."""
from instrument import DECODE
from readers import step_flops


def read(run):
    t = sum(run.spans.durations(DECODE))
    if t <= 0 or not run.spans.decode_calls:
        return None
    ops = sum(step_flops(run.dims, lens) for _, lens in run.spans.decode_calls)
    return 100.0 * ops / (t * run.peaks["bf16_flops"])
