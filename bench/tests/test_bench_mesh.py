"""The four-chip cell's path at smoke size, on four virtual CPU devices in
a process of their own: a closed loop of preloaded clients served through
``ServeEngine(mesh=make_serve_mesh(4)).session()`` reads ``correct``, the
float8 control in its place does not, and neither does each fault a
four-chip cell can have, the exchange between chips left out among them.
CPU only."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
FAULTS = ["exchange-left-out", "half-the-lanes", "state-unchanged",
          "token-altered"]


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(HERE.parents[1] / "src"))
    out = subprocess.run([sys.executable, str(HERE / "mesh_runs.py")],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    return {x["case"]: x for x in lines}


def test_reference_spread_over_chips_equals_one_chip(runs):
    assert runs["spread"]["equal"] is True


def test_sound_four_chip_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"] is True, r["check"]
    assert r["device"]["count"] == 4
    assert r["attempted"] >= 4 and r["failed"] == 0
    # no time to first token: a preloaded closed loop's cell does not list it
    assert r["metrics"] == ["itl_p95_ms", "itl_p95_ms.mesh", "peak_hbm_gib",
                            "setup_s", "tokens_per_s"]
    # a qualified name is the same quantity under a bound of its own
    assert r["values"]["itl_p95_ms.mesh"] == r["values"]["itl_p95_ms"] > 0
    # the preload alone serves each client 3 tokens (its first and a
    # segment of 2), as many as the mix asks of a request in flight
    assert r["check"]["served_tokens_compared"]["value"] >= 4 * 3


def test_control_fails_where_four_chip_program_passes(runs):
    chk = runs["control"]["check"]
    assert runs["control"]["correct"] is True, chk
    assert chk["control_max_logit_gap"]["value"] > \
        chk["max_logit_gap"]["limit"], chk


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_four_chip_path_is_not_correct(runs, fault):
    assert runs[fault]["correct"] is False, runs[fault]["check"]
