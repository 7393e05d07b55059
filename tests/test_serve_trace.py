"""What the serving session shows a profiler and an operator: each serve
program's name (the device trace's module line) and each Pallas kernel's
name, single-device and under a 2-device mesh, the queue-wait counters on
an injected clock, and the count of programs built."""
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import lm_init
from repro.serve import SamplingParams, ServeEngine

REPO = Path(__file__).resolve().parents[1]


def program_texts(engine, waves, **session_kw) -> dict:
    """Serve each wave of prompts to idle and return, by module name, the
    text of every program the session built: a jitted program lowered
    again (with its debug locations) at the shapes of its first call, the
    decode segment's compiled HLO as built."""
    texts = {}
    get_fn = engine._get_fn

    def keep(text):
        name = re.search(r"(?:module @|HloModule )(\w+)", text).group(1)
        texts.setdefault(name, text)

    def spy(key, build):
        fn = get_fn(key, build)
        if isinstance(fn, jax.stages.Compiled):
            keep(fn.as_text())
            return fn

        def call(*args):
            shapes = jax.tree.map(     # keep only a mesh's placements
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding
                    if len(x.sharding.device_set) > 1 else None)
                if isinstance(x, jax.Array) else x, args)
            keep(fn.lower(*shapes).as_text(debug_info=True))
            return fn(*args)
        return call

    engine._get_fn = spy
    try:
        with engine.session(**session_kw) as sess:
            for wave in waves:
                for p in wave:
                    sess.submit(p, SamplingParams(max_tokens=3))
                sess.run_until_idle()
    finally:
        del engine._get_fn
    return texts


def kernel_named(text: str, name: str) -> bool:
    """A Pallas call named ``name`` sits in the program: its name scope
    opens a location or op_name path (``.../packed_xnor_gemv/while/...``),
    unlike a jitted wrapper's ``jit(packed_xnor_gemv)``."""
    return re.search(rf'["/]{name}/', text) is not None


def test_serve_programs_and_kernels_have_stable_names():
    cfg = get_smoke("qwen2.5-14b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_len=64, packed=True,
                         prefix_cache=True)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, 18).astype(np.int32)
    cold = np.concatenate([shared, [5, 6, 7]]).astype(np.int32)
    tail = np.concatenate([shared, [9, 10, 11, 12, 13]]).astype(np.int32)
    # cold prefill; then a partial hit (tail prefill) and an exact hit
    texts = program_texts(engine, [[cold], [tail, cold]], lanes=2,
                          page_size=4, segment=2, buckets=(8, 16, 32, 64))
    assert set(texts) == {"jit_serve_prefill", "jit_serve_tail_prefill",
                          "jit_serve_hit_admit", "jit_serve_decode_segment"}
    seg = texts["jit_serve_decode_segment"]
    assert kernel_named(seg, "packed_xnor_gemv")
    assert kernel_named(seg, "paged_flash_decode")
    assert kernel_named(texts["jit_serve_tail_prefill"],
                        "paged_prefix_attention")
    assert not kernel_named(seg, "_xnor_gemv_kernel")


_MESH = textwrap.dedent("""
    import sys
    sys.path.insert(0, {tests!r})
    import jax, numpy as np
    from repro.configs import get_smoke
    from repro.launch.mesh import make_serve_mesh
    from repro.models import lm_init
    from repro.serve import ServeEngine
    from test_serve_trace import kernel_named, program_texts

    cfg = get_smoke("qwen2.5-14b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_len=32, packed=True,
                         mesh=make_serve_mesh(2))
    texts = program_texts(engine, [[np.arange(7, dtype=np.int32)]],
                          lanes=2, page_size=4, segment=2)
    assert set(texts) == {{"jit_serve_prefill",
                          "jit_serve_decode_segment"}}, sorted(texts)
    for name in ("packed_xnor_gemv", "paged_flash_decode"):
        assert kernel_named(texts["jit_serve_decode_segment"], name), name
    print("NAMES OK")
""")


def test_serve_program_names_hold_under_a_two_device_mesh():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run(
        [sys.executable, "-c", _MESH.format(tests=str(REPO / "tests"))],
        capture_output=True, text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "NAMES OK" in out.stdout


@pytest.mark.parametrize("name", ["packed_xnor_gemv", "packed_xnor_matmul",
                                  "boolean_matmul", "boolean_weight_bwd",
                                  "flash_attention_tpu"])
def test_kernel_names_do_not_follow_python_names(name):
    """Every Pallas call names itself; the device trace names its
    instruction by that name, whatever the wrapper is called."""
    import jax.numpy as jnp
    from repro.kernels import ops

    x = jnp.ones((8, 64), jnp.float32)
    xb = jnp.ones((8, 64), jnp.int8)
    w = jnp.ones((64, 128), jnp.int8)
    pk = ops.pack_bits(w, axis=0)
    q = jnp.ones((2, 128, 64), jnp.float32)
    calls = {
        "packed_xnor_gemv": lambda: ops.packed_xnor_gemv(x, pk, k_valid=64),
        "packed_xnor_matmul": lambda: ops.packed_xnor_matmul(
            ops.pack_bits(xb, axis=1), pk, k_valid=64),
        "boolean_matmul": lambda: ops.boolean_matmul(xb, w),
        "boolean_weight_bwd": lambda: ops.boolean_weight_bwd(
            x, jnp.ones((8, 128), jnp.float32),
            jnp.ones((8, 128), jnp.float32)),
        "flash_attention_tpu": lambda: ops.flash_attention_tpu(q, q, q),
    }
    text = jax.jit(calls[name]).lower().as_text(debug_info=True)
    assert kernel_named(text, name)


def _engine(prefix_cache=False):
    cfg = get_smoke("gemma2-2b")
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    return ServeEngine(cfg, params, max_len=32, prefix_cache=prefix_cache)


def test_queue_wait_counts_each_admission_from_its_last_queue_entry():
    engine = _engine()
    clk = [0.0]
    sess = engine.session(lanes=2, page_size=4, segment=2,
                          clock=lambda: clk[0])
    real = sess._admit_prefill

    def slow(req, eff, o):          # each prefill takes 100 ms
        clk[0] += 100.0
        return real(req, eff, o)

    sess._admit_prefill = slow
    prompts = [np.arange(n, dtype=np.int32) + 1 for n in (5, 7, 6)]
    a = sess.submit(prompts[0], SamplingParams(max_tokens=6))
    b = sess.submit(prompts[1], SamplingParams(max_tokens=6))
    clk[0] = 10.0
    sess.step()                     # admits a, then b behind a's prefill
    st = sess.stats()["sched"]
    assert (st["queue_wait_ms"], st["queue_waited"]) == (10.0 + 110.0, 2)
    assert clk[0] == 210.0
    sess.submit(prompts[2], SamplingParams(max_tokens=4))   # queued at 210
    clk[0] = 300.0
    assert sess.preempt(b)          # b queues again, at 300
    clk[0] = 350.0
    sess.step()                     # b back in its lane; c still waits
    st = sess.stats()["sched"]
    assert (st["queue_wait_ms"], st["queue_waited"]) == (120.0 + 50.0, 3)
    clk[0] = 1000.0
    sess.run_until_idle()           # c admitted once a lane frees
    st = sess.stats()["sched"]
    assert (st["queue_wait_ms"], st["queue_waited"]) == (170.0 + 790.0, 4)
    assert a.status.name == "DONE"
    sess.close()


def test_programs_built_counts_compile_cache_misses():
    engine = _engine(prefix_cache=True)
    assert engine.programs_built == 0
    prompts = [np.arange(n, dtype=np.int32) + 1 for n in (5, 9)]
    for run in range(2):
        with engine.session(lanes=2, page_size=4, segment=2) as sess:
            for p in prompts:
                sess.submit(p, SamplingParams(max_tokens=4))
            sess.run_until_idle()
            built = sess.stats()["engine"]["programs_built"]
        if run == 0:
            first = built
            assert first == len(engine._fns) >= 2
    assert built == first == engine.programs_built   # the rerun built none
