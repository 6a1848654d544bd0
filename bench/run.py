#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the run's checks on standard error, and as the last line of
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end metrics; with ``--trace 1`` the per-layer ones),
``device`` and, traced, ``breakdown``, then ``checks`` (each number
compared with its limit).  Exits 2, printing no result, where JAX finds no
TPU or fewer chips than the cell needs, and 3 where the run did not
measure the fused compressed path (see ``validity.py``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
JAX_CACHE = CHECKOUT / "bench" / ".cache" / "jax"


def _setup_jax():
    """The persistent compile cache is ``JAX_COMPILATION_CACHE_DIR`` where
    the environment sets it, as for the program's own entry points, and
    else a fixed directory in the checkout; it keeps every program of the
    run, however small.  The TPU runtime's logs go into the checkout too,
    unless the environment says where."""
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(JAX_CACHE))
    os.environ.setdefault("TPU_LOG_DIR",
                          str(CHECKOUT / "bench" / ".cache" / "tpu_logs"))
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _setup_jax()
    from bench import harness
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = out["result"]
    if not out["valid"]:
        bad = [f"{n}={v}" for n, v, ok in out["checks"] if not ok]
        print(f"bench: not a measurement of the fused path: "
              f"{'; '.join(bad)}", file=sys.stderr)
        return 3
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, chk in result["checks"].items():
        print(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
