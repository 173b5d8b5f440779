"""Readings that a cell's check limit is set from (run on the chip).

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1 2 3

For each seed, one run of the cell as the benchmark makes it.  On the
tokens that its window served, the program's widest gap below the
float32 reference's best is read and judged (the lower reading).  Then
the control is put in the program's place: the gaps of the tokens that
the reference computed in float8 puts first go through the run's own
comparison, which has to come out not correct (the upper reading).  The
benchmark's own runs never run the control.  One JSON line per seed.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402

from run import configure  # noqa: E402


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    if not configure():
        return 2
    from bench import harness
    bench = harness.load_benchmark()
    limit = harness.Cell.load(bench, args.workload).check["limits"][
        "max_logit_gap"]
    t = T_START
    for seed in args.seeds:
        seen = control_pass(harness, limit)
        t_ref = time.perf_counter()
        out = harness.run_cell(bench, args.workload, seed, args.seconds,
                               False, t, log=lambda *_: None)
        print(json.dumps({"workload": args.workload, "seed": seed, **seen,
                          "control_correct": out["correct"],
                          "control": out["checks"]["max_logit_gap"],
                          "metrics": out["metrics"],
                          "run_s": time.perf_counter() - t_ref}),
              flush=True)
        t = time.perf_counter()
    return 0


def control_pass(harness, limit) -> dict:
    """Put the control in the program's place for the next run: the run
    judges the control's gaps, and ``seen`` gets the program's reading
    and verdict on the same served tokens."""
    gaps = harness.reference_gaps
    seen = {}

    def control(arch, params, mix, checked, mode=None):
        prog = gaps(arch, params, mix, checked)
        ok, gap, served = harness.judge(prog, limit)
        t0 = time.perf_counter()
        ctl = gaps(arch, params, mix, checked, mode="fp8")
        seen.update(program=gap, program_correct=ok, tokens=served,
                    requests=len(prog),
                    control_s=time.perf_counter() - t0)
        harness.reference_gaps = gaps
        return ctl

    harness.reference_gaps = control
    return seen


if __name__ == "__main__":
    sys.exit(main())
