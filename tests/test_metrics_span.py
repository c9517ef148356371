"""``Metrics.span``: the one timer of the program, on the host clock,
and a profiler annotation once JAX is loaded."""
import subprocess
import sys

from repro.core.metrics import SERVE_SPANS, Metrics


def test_span_accumulates_like_the_timer():
    m = Metrics()
    for _ in range(3):
        with m.span("kv.page_out"):
            pass
    with m.timer("cache_flush"):
        pass
    assert m.count["kv.page_out"] == 3 and m.ns["kv.page_out"] > 0
    assert m.count["cache_flush"] == 1 and m.ns["cache_flush"] > 0


def test_span_counts_a_block_that_raises():
    m = Metrics()
    try:
        with m.span("pager.fetch"):
            raise IOError("torn")
    except IOError:
        pass
    assert m.count["pager.fetch"] == 1


def test_serve_spans_are_distinct_exact_names():
    assert len(SERVE_SPANS) == len(set(SERVE_SPANS))
    assert all(" " not in n and n == n.strip() for n in SERVE_SPANS)


def test_the_host_stack_spans_without_importing_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro.core.metrics import Metrics\n"
            "m = Metrics()\n"
            "with m.span('vol.read'):\n"
            "    pass\n"
            "assert m.count['vol.read'] == 1\n"
            "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def _recorded_spans(log_dir):
    """``{span name: [(thread line, start_ns, end_ns)]}`` of the one
    profile recorded under ``log_dir``; a thread line is its plane's name
    and its index there (two threads' lines may share a name)."""
    import glob
    import os

    import jax

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    out = {}
    for plane in jax.profiler.ProfileData.from_file(paths[0]).planes:
        for i, ln in enumerate(plane.lines):
            for ev in ln.events:
                if ev.name in SERVE_SPANS:
                    out.setdefault(ev.name, []).append(
                        ((plane.name, i), ev.start_ns,
                         ev.start_ns + ev.duration_ns))
    return out


def test_spans_land_nested_in_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    m = Metrics()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with m.span("serve.step"):
            for _ in range(2):
                with m.span("lm.decode"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    got = _recorded_spans(str(tmp_path))
    assert len(got["serve.step"]) == 1 and len(got["lm.decode"]) == 2
    line, s0, s1 = got["serve.step"][0]
    assert all(ln == line and s0 <= a <= b <= s1
               for ln, a, b in got["lm.decode"])
    assert m.count["serve.step"] == 1 and m.count["lm.decode"] == 2


def test_a_worker_thread_span_lands_on_its_own_line(tmp_path):
    import threading

    import jax

    m = Metrics()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with m.span("serve.suspend"):
            def op():
                with m.span("vol.read"):
                    pass
            worker = threading.Thread(target=op)
            worker.start()
            worker.join()
    finally:
        jax.profiler.stop_trace()
    got = _recorded_spans(str(tmp_path))
    (main_line, _, _), = got["serve.suspend"]
    (vol_line, _, _), = got["vol.read"]
    assert vol_line != main_line
    assert m.count["vol.read"] == 1
