"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout, on a machine whose JAX finds a TPU (it
exits 2 without printing a result otherwise).  Set-up draws the weights
on the device from the seed, builds the engine from the cell's files,
prefills every session and warms up on the cell's own traffic until a
step builds no new executable.  The window then runs the traffic until
the first step that ends at or after ``--seconds``.  After it the
program's state is freed and the served tokens are compared with the
plain reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles the window and reports its per-layer metrics instead.  Earlier
lines carry the compile counts, the window's counters and the device;
the last lines of stderr give each number compared beside its limit, and
the last line of stdout is the result as one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

CACHE_DIR = HERE / ".jax_cache"

import jax  # noqa: E402

import spec  # noqa: E402

COUNTERS = ("pages_out", "pages_in", "hybrid_attention", "bypass_pages",
            "activate_stalls", "suspends", "resumes", "transit_crc_errors",
            "kv_spills", "kv_restores", "kv_prefetch_issued",
            "kv_prefetch_hits", "kv_dedup_hits", "kv_restore_crc_errors")


class Run:
    """What a per-layer metric reader is given."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)

    def cost(self, kernel: str):
        return spec.cost(kernel)


def use_compile_cache() -> None:
    """JAX's persistent compile cache at one fixed path in the checkout,
    keeping every executable however small or quick to build; the
    program takes the same directory if it asks for one."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def chips_or_exit(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: this cell needs {chips} TPU chip(s); JAX finds "
              f"{len(devs)} {devs[0].platform!r} device(s). There is no "
              f"CPU fallback.", file=sys.stderr)
        sys.exit(2)
    return devs[:chips]


def counters(eng) -> dict:
    with eng.metrics._lock:
        return {k: eng.metrics.count.get(k, 0) for k in COUNTERS}


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def serve_cell(cell: dict, seed: int, seconds: float, traced: bool,
               compiles, log=print) -> dict:
    """Set-up, window and the readings of one run, up to and including
    freeing the program's state.  The phases take their sizes from
    ``cell``, so a CPU test runs them at smoke widths."""
    import serving
    import weights
    from generator import Traffic
    from instrument import SpanLog, instrument
    import devtrace as tracemod

    cfg, wl, tr = cell["config"], cell["workload"], cell["traffic"]
    traffic = Traffic(tr, seed, weights.dims(cfg)["V"])
    params = jax.block_until_ready(weights.init(cfg, seed))
    eng = serving.build_engine(cfg, wl["engine"], traffic.sessions,
                               traffic.max_tokens(), params)
    loop = serving.ClosedLoop(eng, traffic, compiles,
                              preempt_every=tr["preempt_every_steps"])
    spans = None
    if traced:
        spans = SpanLog()
        instrument(eng, spans)
    try:
        loop.fill()
        warm = loop.warm_up(min_steps=wl["warmup_min_steps"])
        gc.collect()
        setup_s = time.perf_counter() - T_START
        setup_compiles = compiles.n
        c0 = counters(eng)
        reduced = None
        if traced:
            with tempfile.TemporaryDirectory() as tdir:
                def window():
                    with jax.profiler.TraceAnnotation(tracemod.WINDOW):
                        return loop.window(seconds)
                start, end = tracemod.capture(window, tdir)
                reduced = tracemod.reduce(tracemod.load(tdir))
            lo, hi = reduced.window
            inside = sum(1 for o in reduced.trace.ops if lo <= o.start <= hi)
            log(f"[trace] window_s {reduced.window_s:.3f} busy_s "
                f"{reduced.busy_s:.6f} device ops {len(reduced.trace.ops)} "
                f"({inside} start inside the window) spans "
                f"{len(reduced.trace.spans)}")
        else:
            start, end = loop.window(seconds)
        c1 = counters(eng)
    finally:
        serving.close_engine(eng)
    win = serving.window_numbers(loop, start, end)
    records = serving.session_records(loop)
    geometry = serving.cache_geometry(cfg, wl["engine"], traffic.max_tokens())
    served_in_window = {rid for rid, s in loop.served.items()
                        if any(t > start for t in s.stamps)}
    for s in loop.served.values():
        s.req = None
    serving.free(params, eng.cache.k_pool, eng.cache.v_pool)
    del eng, loop, params
    gc.collect()
    log(f"[setup] setup_s {setup_s:.3f} warm-up steps {warm} "
        f"executables built in set-up {setup_compiles}")
    log(f"[window] steps {win['steps']} span_s {win['span_s']:.3f} "
        f"tokens {win['tokens']} gaps {len(win['gaps_s'])} "
        f"resumes {len(win['resumes_s'])} compiles {win['compiles']} "
        f"step ends_s {[round(t - start, 3) for t in win['ends']]}")
    diff = {k: c1[k] - c0[k] for k in COUNTERS}
    diff["prefetch_hit_rate"] = (diff["kv_prefetch_hits"] / diff["kv_restores"]
                                 if diff["kv_restores"] else None)
    log("[counters] " + json.dumps(diff))
    return {"setup_s": setup_s, "window": win, "counters": diff,
            "records": records, "geometry": geometry, "spans": spans,
            "reduced": reduced, "window_start": start,
            "served_in_window": served_in_window, "totals": c1}


def compare(cell: dict, seed: int, out: dict, control: bool = False) -> dict:
    """Each number compared with its limit, and the verdict.  With
    ``control`` the float8 control's first tokens stand in the program's
    place (``chipbench/control.py``); ``rows`` then hold both readings."""
    import checks

    rows = checks.gaps(cell["config"], seed,
                       checks.sample(out["records"], seed), control=control)
    key = "control_gap" if control else "gap"
    s = checks.summary(rows, key)
    limit = cell["workload"]["limits"]["max_logit_gap"]
    c = out["totals"]                     # the whole run, set-up included
    crc = c["kv_restore_crc_errors"] + c["transit_crc_errors"]
    numbers = {"max_logit_gap": {"value": s["max"], "limit": limit},
               "crc_errors": {"value": crc, "limit": 0}}
    ok = s["max"] is not None and s["max"] <= limit and crc == 0
    bad = {r["req_id"] for r in rows if float(r[key].max()) > limit}
    return {"correct": bool(ok), "numbers": numbers, "summary": s,
            "rows": rows,
            "attempted": len(out["served_in_window"]),
            "failed": len(bad & out["served_in_window"])}


def end_to_end(cell: dict, out: dict) -> dict:
    import serving

    win = out["window"]
    vals = {"setup_s": out["setup_s"],
            "decode_tok_s": win["tokens"] / win["span_s"],
            "itl_p95_ms": (1e3 * serving.p95(win["gaps_s"])
                           if win["gaps_s"] else None),
            "resume_p95_ms": (1e3 * serving.p95(win["resumes_s"])
                              if win["resumes_s"] else None)}
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"] if vals.get(m["name"]) is not None}


def per_layer(cell: dict, out: dict, dims: dict, peaks: dict) -> dict:
    run = Run(trace=out["reduced"],
              spans=out["spans"].since(out["window_start"]),
              counters=out["counters"], geometry=out["geometry"],
              dims=dims, peaks=peaks)
    res = {}
    for m in cell["per_layer"]:
        v = spec.metric(m["name"]).read(run)
        if v is not None:
            res[m["name"]] = {"value": v, "unit": m["unit"]}
    return res


def run_cell(cell: dict, seed: int, seconds: float, traced: bool, devs,
             peaks: dict, log=print) -> dict:
    """One whole run on ``devs`` after the look for a chip: the result
    line as a dict, ``checks`` last."""
    import serving
    import weights

    compiles = serving.CompileCounter()
    out = serve_cell(cell, seed, seconds, traced, compiles, log=log)
    peak = memory_peak(devs)
    verdict = compare(cell, seed, out)
    s = verdict["summary"]
    log(f"[correct] widest gap by source: prefill {s['prefill']} "
        f"resident {s['resident']} after_round_trip "
        f"{s['after_round_trip']} over {s['tokens']} served tokens")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": verdict["correct"],
              "attempted": verdict["attempted"],
              "failed": verdict["failed"]}
    if traced:
        red = out["reduced"]
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["metrics"] = per_layer(cell, out, weights.dims(cell["config"]),
                                      peaks)
        result["breakdown"] = {"device_ops": red.top_ops(),
                               "idle_gaps": red.top_idle()}
    else:
        result["metrics"] = end_to_end(cell, out)
    result["device"] = device
    result["checks"] = verdict["numbers"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    cell = spec.cell(args.workload)
    devs = chips_or_exit(cell["chips"])
    peaks = spec.peaks(devs[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                      peaks, log=lambda line: print(line, flush=True))
    for name, n in result["checks"].items():
        print(f"[check] {name} {n['value']} limit {n['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
