"""The float32 reference against the program's prefill-then-decode logits
at smoke size, for a dense and an SSM configuration, and the float8
control against both. CPU only."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import program as P  # noqa: E402
import reference as R  # noqa: E402

DATA = BENCH / "tests" / "data"

# Tolerances, as the largest |program - reference| over every logit,
# relative to the largest reference logit:
# * float32 program: both sides multiply exact ±1 weights and sum in
#   float32, only in another order: about sqrt(K)·2^-24 per dot, which two
#   layers and the head keep under 1e-5 relative; 1e-4 leaves room.
# * bfloat16 program (the served dtype): activations are rounded to 8
#   mantissa bits between operations (2^-9 relative each); a few dozen such
#   roundings on the path to a logit stay under 3% of its scale.
CASES = [("dense-smoke", "float32", 1e-4), ("ssm-smoke", "float32", 1e-4),
         ("dense-smoke", "bfloat16", 3e-2), ("ssm-smoke", "bfloat16", 3e-2)]


def program_logits(cfg, seed, prompt, served):
    """Logits of the prompt's last position and of each decode step,
    through the program's own prefill and cached decode."""
    from repro.models import cache_init, lm_decode_step, lm_prefill

    mc = P.model_config(cfg)
    params = P.make_params(mc, seed)
    from repro.serve.engine import pack_weights

    params = pack_weights(params)
    cache = cache_init(mc, 1, cfg["serve"]["max_len"])[0]
    lg, cache = lm_prefill(mc, params, {"tokens": jnp.asarray([prompt])},
                           cache=cache)
    out = [lg[0, -1]]
    for t in served[:-1]:
        lg, cache = lm_decode_step(mc, params, cache,
                                   jnp.asarray([[t]], jnp.int32))
        out.append(lg[0, -1])
    return np.stack([np.asarray(x, np.float32)[:cfg["vocab_size"]]
                     for x in out])


def reference_logits(cfg, seed, prompt, served):
    arch = R.Arch(cfg)
    seq = list(prompt) + list(served[:-1])
    h = R.final_hidden(arch, seed, [seq])[0]
    head = R._head(arch, R.W.seed_key(seed))
    rows = h[len(prompt) - 1:]
    return np.asarray(R._logits(arch, head, rows, "f32"))


@pytest.mark.parametrize("name,dtype,tol", CASES)
def test_reference_matches_program(name, dtype, tol):
    cfg = json.loads((DATA / f"{name}.json").read_text())
    cfg["program"] = dict(cfg["program"], overrides=dict(
        cfg["program"].get("overrides", {}), dtype=dtype))
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg["vocab_size"], 23)
    served = rng.integers(0, cfg["vocab_size"], 9)
    got = program_logits(cfg, 4, prompt, served)
    want = reference_logits(cfg, 4, prompt, served)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err <= tol, err


@pytest.mark.parametrize("name", ["dense-smoke", "ssm-smoke"])
def test_gaps_of_reference_tokens_are_zero(name):
    """The reference's own greedy tokens lie at gap 0 by construction."""
    cfg = json.loads((DATA / f"{name}.json").read_text())
    arch = R.Arch(cfg)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg["vocab_size"], 12)
    served = []
    for _ in range(6):
        seq = list(prompt) + served
        h = R.final_hidden(arch, 9, [seq])[0]
        lg = R._logits(arch, R._head(arch, R.W.seed_key(9)), h[-1:], "f32")
        served.append(int(jnp.argmax(lg[0])))
    gaps = R.served_gaps(arch, 9, [(prompt, np.asarray(served))])
    assert float(gaps[0].max()) == pytest.approx(0.0, abs=1e-6)


def test_fp8_rounds_to_three_mantissa_bits():
    x = jnp.asarray([1.0, 1.0625, 1.125, 0.3, -500.0, 2.0 ** -9, 0.0])
    got = np.asarray(R.fp8(x))
    np.testing.assert_allclose(got, [1.0, 1.0, 1.125, 0.3125, -448.0,
                                     2.0 ** -9, 0.0])


@pytest.mark.parametrize("mode", ["f32", "fp8"])
def test_blocked_attention_equals_unblocked(mode):
    """Attention over blocks of query rows (how the reference fits a chip
    at 10,240 positions) gives the hidden states of attention over all
    rows at once, to the bit."""
    import family

    cfg = json.loads((DATA / "dense-smoke.json").read_text())
    whole, blocked = R.Arch(cfg), R.Arch(cfg)
    blocked.score_bytes = cfg["num_attention_heads"] * 64 * 4 * 8
    dense = family.load("dense")
    assert dense.query_rows(64, whole.heads, whole.score_bytes) == 64
    assert dense.query_rows(64, blocked.heads, blocked.score_bytes) == 8
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, cfg["vocab_size"], n) for n in (50, 17)]
    a = R.final_hidden(whole, 6, seqs, mode, pad_to=64)
    b = R.final_hidden(blocked, 6, seqs, mode, pad_to=64)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_query_rows_at_the_cells_sizes():
    """qwen2.5-14b's 40 heads: 2,048 positions attend in one block, as the
    reference always has; 10,240 in blocks whose scores fit 1 GiB."""
    import family

    dense = family.load("dense")
    assert dense.query_rows(2048, 40, dense.SCORE_BYTES) == 2048
    rows = dense.query_rows(10240, 40, dense.SCORE_BYTES)
    assert 10240 % rows == 0 and 40 * rows * 10240 * 4 <= dense.SCORE_BYTES
    assert rows >= 512
