"""Whole smoke runs of the four-chip cell on four virtual CPU devices, one
JSON line each: the sound program, the sound program beside the float8
control, then each fault a four-chip serving cell can have, planted under
the timed path. Run by
``test_bench_mesh.py`` in a process of its own (the test process keeps
one device):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/mesh_runs.py
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import reference as R  # noqa: E402
import smoke  # noqa: E402
from test_bench_faults import FAULTS  # noqa: E402

SEED = 2**32 + 9


def _exchange_left_out(monkeypatch):
    """The all-gather of the attention heads before the replicated
    o-projection is left out: each chip projects its own heads, with the
    other chips' heads zero."""
    import repro.models.attention as A

    orig = A._wo_project

    def local(cfg, p_wo, out):
        if cfg.serve_tp == 1:
            return orig(cfg, p_wo, out)
        n = out.shape[2]
        full = jnp.zeros(out.shape[:2] + (n * cfg.serve_tp,), out.dtype)
        at = jax.lax.axis_index("model") * n
        full = jax.lax.dynamic_update_slice_in_dim(full, out, at, axis=2)
        return A.proj_apply(cfg, p_wo, full)

    monkeypatch.setattr(A, "_wo_project", local)


CASES = dict(FAULTS, **{"exchange-left-out": _exchange_left_out})


def spread_equals_one_device() -> bool:
    """The reference's sequences spread over the four devices give the
    hidden states they give on one."""
    cfg = json.loads((smoke.DATA / "dense-tp-smoke.json").read_text())
    arch = R.Arch(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 128, n) for n in (30, 7, 19, 44, 3)]
    a = R.final_hidden(arch, 5, seqs, pad_to=64)
    b = R.final_hidden(arch, 5, seqs, pad_to=64, devices=jax.devices()[:4])
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def main():
    assert len(jax.devices()) >= 4, jax.devices()
    print(json.dumps({"case": "spread", "equal": spread_equals_one_device()}),
          flush=True)
    for case in ["sound", "control"] + sorted(CASES):
        with pytest.MonkeyPatch.context() as mp:
            if case in CASES:
                CASES[case](mp)
            out = smoke.run("tp", SEED, control=case == "control")
        print(json.dumps({"case": case, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "metrics": sorted(out["metrics"]),
                          "values": {k: m["value"]
                                     for k, m in out["metrics"].items()},
                          "device": out["device"], "check": out["check"]}),
              flush=True)


if __name__ == "__main__":
    main()
