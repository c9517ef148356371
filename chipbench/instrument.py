"""Host spans around the calls into each layer, from the benchmark's side.

In a traced run the harness replaces a few methods on the engine's own
instances with wrappers that time the call on the host clock, put a
``bench:<name>`` annotation into the profiler trace, and (where asked)
block on the result, so that the device time of the call lands inside
its span.  Nothing of the program is edited.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np

STEP = "ServeEngine.step"
DECODE = "PagedLM.decode_step"
PREFILL = "PagedLM.prefill"
SUSPEND = "ServeEngine.suspend"
ACTIVATE = "PagedKVCache.activate"


@dataclass
class SpanLog:
    spans: list[tuple[str, float, float, int]] = field(default_factory=list)
    # (host time, the cache length of each sequence) per decode call
    decode_calls: list[tuple[float, list[int]]] = field(default_factory=list)
    _depth: int = 0

    def wrap(self, obj, attr: str, name: str, *, block: bool = False,
             on_call=None) -> None:
        orig = getattr(obj, attr)

        def wrapper(*args, **kwargs):
            if on_call is not None:
                on_call(*args, **kwargs)
            depth = self._depth
            self._depth += 1
            with jax.profiler.TraceAnnotation("bench:" + name):
                t0 = time.perf_counter()
                try:
                    out = orig(*args, **kwargs)
                    if block:
                        out = jax.block_until_ready(out)
                finally:
                    self._depth = depth
                    self.spans.append((name, t0, time.perf_counter(), depth))
            return out

        setattr(obj, attr, wrapper)

    def since(self, t: float) -> "SpanLog":
        """The spans that started at or after ``t``, and the decode calls
        made then."""
        return SpanLog([s for s in self.spans if s[1] >= t],
                       [c for c in self.decode_calls if c[0] >= t])

    def durations(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1, _ in self.spans if n == name]

    def self_times(self, name: str) -> list[float]:
        """Each ``name`` span minus the spans directly inside it."""
        out = []
        for n, t0, t1, d in self.spans:
            if n != name:
                continue
            inner = sum(c1 - c0 for _, c0, c1, cd in self.spans
                        if cd == d + 1 and c0 >= t0 and c1 <= t1)
            out.append((t1 - t0) - inner)
        return out


def instrument(eng, log: SpanLog) -> None:
    def on_decode(tokens, sids, positions):
        log.decode_calls.append((time.perf_counter(),
                               [int(p) + 1 for p in np.asarray(positions)]))

    log.wrap(eng, "step", STEP)
    log.wrap(eng, "suspend", SUSPEND)
    log.wrap(eng.lm, "decode_step", DECODE, block=True, on_call=on_decode)
    log.wrap(eng.lm, "prefill", PREFILL, block=True)
    log.wrap(eng.cache, "activate", ACTIVATE)
