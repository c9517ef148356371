"""``chip_smoke.py`` rehearsed on the CPU: both phases at smoke widths
(kernels in interpret mode), and the entry point's refusal to run without
a TPU."""
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402


def test_kernel_phase_cpu_rehearsal():
    out = chip_smoke.kernel_phase(pool_pages=16, page=8, row=128,
                                  n_ids=(1, 3), batch=2, heads=4,
                                  kv_heads=2, head_dim=128, max_pages=4)
    assert [r["n_ids"] for r in out["codec"]] == [1, 3]
    assert all(r["max_lsb"] <= 1 for r in out["codec"])
    assert out["paged_attention"]["max_abs_err"] <= chip_smoke.BF16_TOL
    # interpret mode here: the lowering carries no Mosaic call
    assert sorted(out["mosaic"]) == ["gather_quantize_crc",
                                     "paged_attention",
                                     "scatter_dequantize_crc"]
    assert not any(out["mosaic"].values())


def test_serving_phase_cpu_rehearsal():
    res = chip_smoke.serving_phase(
        get_config("qwen2.5-3b", smoke=True), n_requests=3, prompt_len=16,
        max_new=8, pool_pages=32, page_size=8, host_pages=1,
        suspend_every=3)
    assert res["tokens"] == [8, 8, 8]
    assert res["kv_spills"] > 0 and res["kv_restores"] > 0
    assert res["suspends"] > 0 and res["resumes"] == res["suspends"]
    assert res["hybrid_attention"] == 0
    assert res["logits"]["top1"] == res["logits"]["dense_top1"]


def _run_smoke(script: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(script))


def test_chip_smoke_refuses_cpu():
    out = _run_smoke(os.path.join(REPO, "chip_smoke.py"))
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """Without the rest of the repository the script has no program to
    run: it exits nonzero and prints no result."""
    script = tmp_path / "chip_smoke.py"
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), script)
    out = _run_smoke(str(script))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
