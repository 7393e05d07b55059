"""Shared by the tests that drive a whole run at smoke size on the CPU."""
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
CELLS = {"dense": "dense-smoke.chat-prefix-smoke",
         "ssm": "ssm-smoke.chat-burst-smoke",
         "tp": "dense-tp-smoke.long-decode-smoke"}   # four devices


def run(kind: str, seed: int, **kw) -> dict:
    """One run of a smoke cell, past the harness's look for a chip."""
    import harness

    bench = harness.load_json(DATA / "bench-smoke.json")
    kw.setdefault("log", lambda m: None)
    return harness.run_cell(bench, CELLS[kind], seed, 1.0, False,
                            BENCH.parent, time.perf_counter(),
                            require_chip=False, traffic_dir=DATA,
                            cells_dir=DATA / "cells", **kw)
