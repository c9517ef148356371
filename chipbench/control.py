"""Readings that set a cell's correctness limit, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

For each seed, in one process: the cell's set-up and a window of
``--seconds`` at the cell's own load, then the harness's own comparison
(``run.compare``) with the tokens that the float8 control puts first in
the program's place.  It prints the control's verdict, which has to be
``correct: false``, its widest gap (the control's reading) and the
widest gap of the served tokens (the program's reading).  The limit lies
between the largest program reading over a dozen seeds or more and the
smallest control reading.  The benchmark's own runs never run the
control.  Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import checks
    import serving

    run.use_compile_cache()
    cell = run.spec.cell(args.workload)
    run.chips_or_exit(cell["chips"])
    compiles = serving.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.serve_cell(cell, seed, args.seconds, False, compiles,
                             log=lambda line: print(line, flush=True))
        v = run.compare(cell, seed, out, control=True)
        line = {"seed": seed, "control_correct": v["correct"],
                "checks": v["numbers"],
                "program": checks.summary(v["rows"], "gap"),
                "control": v["summary"]}
        print("[reading] " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
