"""chip_smoke.py on the CPU: its serving and kernel-check functions run at
the smoke config, and the script itself refuses to report a result
without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_smoke

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def engine(smoke):
    from repro.models import lm_init
    from repro.serve import ServeEngine

    cfg = get_smoke("qwen2.5-14b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    return ServeEngine(cfg, params, max_len=64, packed=True,
                       prefix_cache=True)


def test_serve_runs_the_smoke_traffic_shape(smoke, engine):
    """The smoke traffic through the same ``serve``: every request DONE
    with its budget of in-vocab tokens, shared-prefix requests admitted
    after the first wave are partial hits, and a rerun reproduces every
    stream."""
    prompts = smoke.make_traffic(engine.cfg.vocab_size, 6, shared_len=16,
                                 lo=8, hi=40, tail=(5, 12), seed=3)
    kw = dict(gen=4, lanes=2, page_size=4, segment=2, buckets=(16, 32, 64))
    first = smoke.serve(engine, prompts, **kw)
    assert first["prefix"]["partial_hits"] >= 1
    again = smoke.serve(engine, prompts, **kw)
    for a, b in zip(first["streams"], again["streams"]):
        assert a.tolist() == b.tolist()
    hlo = smoke._segment_hlo(engine, again["pool_key"])
    assert "tpu_custom_call" not in hlo          # interpreted on the CPU


def test_check_kernels_passes_at_small_shapes(smoke, engine):
    smoke.check_kernels(engine, lanes=2, max_len=64, page=4, prefix_len=16,
                        tail=8)


def test_script_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_PAGED_KERNEL", None)
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=str(REPO))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr
