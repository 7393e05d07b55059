"""Calibrate the correctness limit of a cell: for each seed, one run of
the cell's traffic with a short window, the program's reading, and the
float8 control's reading on the same sample.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

All seeds run in one process (set-up is long; compiled programs are
shared). Prints one JSON line per seed. The benchmark's own runs never
run the control; ``PERF.md`` records the readings each limit was set from.
"""
from __future__ import annotations

import time

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(Path(__file__).resolve().parent), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import harness

    bench = harness.load_json(ROOT / "BENCHMARK.json")
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(bench, args.workload, seed, args.seconds,
                               False, ROOT, time.perf_counter(),
                               control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "check": out["check"],
                          "metrics": out["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
