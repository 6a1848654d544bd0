#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 11 12 ... --control-seeds 11 12 13

For each seed, a whole run of the cell as ``run.py`` makes it (its own
weights, packed; warm-up; a window of ``--seconds`` at the cell's load;
the reference over a sample of the finished requests), printing the
program's widest logit gap.  For each control seed it also prints the
control's: the reference with every matrix the program quantizes rounded
to 4 bits (the step below the served int8 weights), read at the same
positions, as the gap of the token the control puts first.  The limit
goes above the program's largest reading and below the control's
smallest.  The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as entry  # noqa: E402  (bench/run.py, beside this file)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    ap.add_argument("--bits", type=int, default=4)
    args = ap.parse_args(argv)
    entry._setup_jax()
    import numpy as np
    from bench import correctness, harness, spec
    w = spec.workload(args.workload)
    c = spec.config(w["config"])
    model = spec.model(c["model_type"])
    t_start = T_START
    for seed in args.seeds:
        out = harness.run(args.workload, seed, args.seconds, False,
                          t_start=t_start)
        r = out["result"]
        line = {"seed": seed, "valid": out["valid"],
                "program_gap": r["checks"]["logit_gap"]["value"],
                "tokens": len(out["picked"]) and sum(
                    p.completion.n_generated for p in out["picked"]),
                "metrics": {k: v["value"] for k, v in r["metrics"].items()}}
        if seed in args.control_seeds and out["picked"]:
            seqs, rows, served = correctness.served_rows(out["picked"])
            ref = correctness.reference_logits(model, c, seed, seqs, rows)
            ctl = np.asarray(correctness.reference_logits(
                model, c, seed, seqs, rows, bits=args.bits))
            line["control_gap"] = float(
                correctness.gaps(ref, ctl.argmax(1)).max())
        print(json.dumps(line), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
