"""The trace reduction and the kernel cost functions, on the CPU."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import spec  # noqa: E402
import devtrace as tracemod  # noqa: E402
from devtrace import Op, Span, Trace  # noqa: E402


def _small_trace() -> Trace:
    """A window of 10 s on one device: ops at [1,3] and [2,4] overlap
    (busy 3 s), a kernel at [6,7], a tail op at [9.5,11] half outside.
    Host: a step span [0,8] holding a decode span [1,5] and a restore
    span [5.5,7.5]."""
    return Trace(
        ops=[Op("fusion.1", "jit_matmul", 1.0, 3.0),
             Op("fusion.2", "jit_matmul", 2.0, 4.0),
             Op("paged_kernel", "jit_paged_attention", 6.0, 7.0),
             Op("copy", "jit_copy", 9.5, 11.0),
             Op("early", "jit_x", -2.0, -1.0)],
        spans=[Span(tracemod.WINDOW, 0.0, 10.0),
               Span("bench:ServeEngine.step", 0.0, 8.0),
               Span("bench:PagedLM.decode_step", 1.0, 5.0),
               Span("bench:PagedKVCache.activate", 5.5, 7.5)])


def test_busy_is_the_union_inside_the_window():
    red = tracemod.reduce(_small_trace())
    assert red.window_s == pytest.approx(10.0)
    # [1,4] + [6,7] + [9.5,10] clipped to the window
    assert red.busy_s == pytest.approx(3.0 + 1.0 + 0.5)


def test_idle_gaps_go_to_the_innermost_span():
    red = tracemod.reduce(_small_trace())
    idle = red.idle_by_span
    # gaps: [0,1] step, [4,6] mid 5 -> decode, [7,9.5] mid 8.25 -> window
    assert idle["bench:ServeEngine.step"] == pytest.approx(1.0)
    assert idle["bench:PagedLM.decode_step"] == pytest.approx(2.0)
    assert idle[tracemod.WINDOW] == pytest.approx(2.5)
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
    assert red.top_idle(1) == [[tracemod.WINDOW, pytest.approx(2.5)]]


def test_kernel_time_by_name_or_module():
    red = tracemod.reduce(_small_trace())
    assert red.kernel_s(("paged_attention",)) == pytest.approx(1.0)
    assert red.kernel_s(("fusion",)) == pytest.approx(3.0)
    assert red.kernel_s(("absent",)) == 0.0
    assert red.op_s["jit_copy/copy"] == pytest.approx(0.5)
    assert not any("early" in k for k in red.op_s)


def test_op_name_is_the_instruction_not_its_operands():
    text = ("%reshape.3 = bf16[4,16,128]{2,1,0} reshape(bf16[4,2,8,128] "
            "%paged_attention.1)")
    assert tracemod.op_name(text) == "reshape.3"
    assert tracemod.op_name("fusion.2") == "fusion.2"


def test_union_merges_touching_and_nested():
    assert tracemod.union([(3, 4), (0, 2), (1, 1.5), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]


def test_a_recorded_trace_loads_its_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()

    def window():
        with jax.profiler.TraceAnnotation(tracemod.WINDOW):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench:ServeEngine.step"):
                    f(x).block_until_ready()

    tracemod.capture(window, str(tmp_path))
    tr = tracemod.load(str(tmp_path))
    names = [s.name for s in tr.spans]
    assert names.count(tracemod.WINDOW) == 1
    assert names.count("bench:ServeEngine.step") == 2
    red = tracemod.reduce(tr)
    assert red.window_s > 0
    w0, w1 = red.window
    steps = [s for s in tr.spans if s.name == "bench:ServeEngine.step"]
    assert all(w0 <= s.start <= s.end <= w1 for s in steps)


GEOM = {"L": 36, "Hkv": 2, "hd": 128, "page": 16, "bytes_per_elem": 2,
        "pages_per_seq": 68, "pool_pages": 340}


def test_paged_attention_counts_valid_pages_only():
    cost = spec.cost("paged_attention")
    ops, nbytes = cost.work(GEOM, {"H": 16}, [1, 16, 17])
    pages = 1 + 1 + 2                     # not 3 x 68 grid steps
    kv = pages * 16 * 2 * 128 * 2 * 2
    q_out = 2 * 3 * 16 * 128 * 2
    assert nbytes == kv + q_out + 4 * (pages + 3)
    assert ops == 4 * 16 * 128 * (1 + 16 + 17)
    # the same lengths cost the same whatever the table bound
    assert cost.work(dict(GEOM, pages_per_seq=260), {"H": 16},
                     [1, 16, 17]) == (ops, nbytes)


def test_codec_counts_one_page_each_way():
    cost = spec.cost("codec")
    elems = 16 * 2 * 128
    packed = elems + 4 * 16 + 4
    assert cost.page_out(GEOM) == (8.0 * elems, float(2 * elems + packed))
    assert cost.page_in(GEOM) == (8.0 * elems, float(packed + 2 * elems))


def test_roofline_is_the_larger_bound_over_the_time():
    from readers import roofline
    peaks = spec.peaks("TPU v5 lite")
    # 819 MB at 819 GB/s is 1 ms; 1 ms of device time -> 100%
    assert roofline(0.0, 819e6, 1e-3, peaks) == pytest.approx(100.0)
    assert roofline(197e9, 0.0, 4e-3, peaks) == pytest.approx(25.0)
    assert roofline(0.0, 0.0, 1.0, peaks) is None
    assert roofline(1.0, 1.0, 0.0, peaks) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")
