"""Each cell's run rehearsed on the CPU at smoke widths, end to end: the
set-up, the window, the reading of the trace and the comparison with the
reference, and the result line.  The look for a chip is skipped; the
kernels run in interpret mode.  Then faults planted under the timed path
must turn ``correct`` false, and so must the float8 control."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import checks  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**33 + 11                 # wider than 32 bits
PEAKS = spec.peaks("TPU v5 lite")


def smoke(name: str, width: int = 64, layers: int = 2) -> dict:
    """The cell at smoke widths: its own traffic kind, engine and limit,
    with short lengths and a small page."""
    cell = spec.cell(name)
    cfg = cell["config"]
    kv = max(1, 4 * cfg["num_key_value_heads"] // cfg["num_attention_heads"])
    cell["config"] = dict(cfg, hidden_size=width, num_attention_heads=4,
                          num_key_value_heads=kv, num_hidden_layers=layers,
                          intermediate_size=2 * width, vocab_size=512)
    cell["traffic"] = dict(
        cell["traffic"],
        prompt_tokens={"min": 8, "max": 16},
        answer_tokens={"min": 16, "max": 48})
    wl = dict(cell["workload"])
    wl["engine"] = dict(wl["engine"], page_size=8,
                        host_pages=min(1, wl["engine"]["host_pages"]))
    cell["workload"] = wl
    return cell


def _line(cell, traced=False, seconds=0.3):
    res = run.run_cell(cell, SEED, seconds, traced, jax.devices()[:1],
                       PEAKS, log=lambda s: None)
    return json.loads(json.dumps(res))


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal_line_is_well_formed(name):
    cell = smoke(name)
    line = _line(cell)
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    want = {m["name"] for m in cell["end_to_end"]}
    assert set(line["metrics"]) == want
    for m in cell["end_to_end"]:
        v = line["metrics"][m["name"]]
        assert v["unit"] == m["unit"] and v["value"] > 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert line["checks"]["max_logit_gap"]["limit"] == \
        cell["workload"]["limits"]["max_logit_gap"]


def test_traced_rehearsal_reports_per_layer_metrics():
    cell = smoke(CELLS[0])
    line = _line(cell, traced=True)
    assert line["correct"] is True
    names = {m["name"] for m in cell["per_layer"]}
    got = set(line["metrics"])
    assert got <= names
    # spans and counters read on any platform; the device ones need a chip
    for m in ("sched_self_ms", "decode_step_ms", "decode_mfu"):
        assert m in got
    assert not got & {"device_idle", "paged_attention_roofline"}
    assert {"window_s", "busy_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "checks"


def _token_plus_one(monkeypatch):
    from repro.serve.engine import ServeEngine
    orig = ServeEngine._sample
    monkeypatch.setattr(ServeEngine, "_sample", lambda self, lg, reqs:
                        (orig(self, lg, reqs) + 1) % 512)


def _state_unchanged(monkeypatch):
    from repro.serve.kvcache import PagedKVCache
    orig = PagedKVCache.append_token
    monkeypatch.setattr(PagedKVCache, "append_token",
                        lambda self, sid, k, v: orig(
                            self, sid, [x * 0 for x in k], [x * 0 for x in v]))


def _half_batch(monkeypatch):
    from repro.serve.engine import PagedLM
    orig = PagedLM.decode_step

    def half(self, tokens, sids, positions):
        lg = orig(self, tokens, sids, positions)
        h = (lg.shape[0] + 1) // 2
        return jnp.concatenate([lg[:h], lg[:lg.shape[0] - h]], 0)
    monkeypatch.setattr(PagedLM, "decode_step", half)


def _restore_lost(monkeypatch):
    import repro.serve.kvcache as kvcache
    orig = kvcache.scatter_dequantize_crc
    monkeypatch.setattr(kvcache, "scatter_dequantize_crc",
                        lambda pool, ids, q, s: orig(pool, ids, q, s * 0))


FAULTS = {"token_altered": _token_plus_one,
          "state_unchanged": _state_unchanged,
          "half_batch": _half_batch,
          "restore_lost": _restore_lost}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    name = [c for c in CELLS if spec.cell(c)["traffic"]["preempt_every_steps"]]
    cell = smoke(name[0] if name else CELLS[0])
    FAULTS[fault](monkeypatch)
    line = _line(cell, seconds=0.5)
    assert line["correct"] is False
    n = line["checks"]["max_logit_gap"]
    assert n["value"] > n["limit"]


def test_codec_mask_marks_keys_paged_out_before_the_query():
    m = checks.codec_mask(6, [2, 4])
    assert not m[:2].any()                    # before any suspension
    assert m[2].tolist() == [1, 1, 0, 0, 0, 0]
    assert m[3].tolist() == [1, 1, 0, 0, 0, 0]
    assert m[5].tolist() == [1, 1, 1, 1, 0, 0]


def test_sample_keeps_the_longest_request():
    recs = [{"req_id": i, "prompt": [0], "tokens": [0] * n}
            for i, n in enumerate([5, 50, 7, 9])]
    assert checks.sample(recs, 1) == recs
    got = checks.sample(recs, 1, max_tokens=60)
    assert got[0]["req_id"] == 1 and sum(len(r["tokens"]) for r in got) <= 60


@pytest.mark.parametrize("seed", [7, 2**31 + 5, 3 * 2**32 + 1])
def test_float8_control_reads_worse_than_the_program(seed):
    """The control (the reference in float8, put in the program's place
    by the harness's own comparison) at smoke widths: at every served
    position it reads the reference's gap for the token it puts first.
    Its widest gap is at least three times the program's, the rule the
    cell's limit is set by."""
    import serving
    cell = smoke(CELLS[0])
    out = run.serve_cell(cell, seed, 0.3, False, serving.CompileCounter(),
                         log=lambda s: None)
    v = run.compare(cell, seed, out, control=True)
    prog = checks.summary(v["rows"], "gap")["max"]
    ctl = v["numbers"]["max_logit_gap"]["value"]
    assert ctl == v["summary"]["max"]
    assert ctl > 0 and ctl >= 3 * prog, (prog, ctl)
