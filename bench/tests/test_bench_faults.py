"""A whole run at smoke size with the timed path broken underneath:
``correct`` comes out false for each fault a serving cell can have, and
true for the sound program. (The cells here run on one chip, so there is
no exchange between chips to leave out.) The smoke configurations compare
every finished request (``correct.sample``), so whether a fault is seen
does not hang on which lanes the sampled requests happened to run in.
CPU only."""
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parent)]

import smoke  # noqa: E402


def _token_altered(monkeypatch):
    """Every decoded token is altered where it is produced."""
    import repro.serve.engine as E

    orig = E.sample_tokens

    def altered(cfg, *a, **kw):
        return (orig(cfg, *a, **kw) + 1) % cfg.vocab_size

    monkeypatch.setattr(E, "sample_tokens", altered)


def _state_unchanged(monkeypatch):
    """The decode step returns the cache it was given: no KV row or SSM
    state of a decoded token is ever written."""
    import repro.serve.engine as E

    orig = E.lm_decode_step_paged

    def frozen(cfg, params, cache, tokens):
        logits, new = orig(cfg, params, cache, tokens)
        return logits, dict(new, blocks=cache["blocks"])

    monkeypatch.setattr(E, "lm_decode_step_paged", frozen)


def _half_the_lanes(monkeypatch):
    """Half of the lanes are left out of the step: they take the first
    lane's logits in place of their own."""
    import repro.serve.engine as E

    orig = E.lm_decode_step_paged

    def half(cfg, params, cache, tokens):
        logits, new = orig(cfg, params, cache, tokens)
        n = logits.shape[0]
        keep = (jnp.arange(n) < n // 2)[:, None, None]
        return jnp.where(keep, logits, logits[:1]), new

    monkeypatch.setattr(E, "lm_decode_step_paged", half)


FAULTS = {"token-altered": _token_altered,
          "state-unchanged": _state_unchanged,
          "half-the-lanes": _half_the_lanes}


@pytest.mark.parametrize("kind", ["dense", "ssm"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_path_is_not_correct(kind, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = smoke.run(kind, 3)
    assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("kind", ["dense", "ssm"])
def test_sound_run_is_correct(kind):
    out = smoke.run(kind, 3)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "tokens_per_s", "peak_hbm_gib",
                                   "setup_s"}
    assert list(out)[-1] == "check"
