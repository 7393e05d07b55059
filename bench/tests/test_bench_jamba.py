"""The jamba family (AI21-Jamba2-3B's hybrid block without experts): its
reference leaves are the program's served tree at the published widths,
and a smoke model, one period of 14 blocks with one KV head, dt/B/C norms
on and no RoPE, served through the paged session with the prefix cache on
agrees with the family's float32 reference, while the same model with
RoPE left on, or without the norms, does not. CPU only."""
import json
import sys
import time
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import family  # noqa: E402
import harness  # noqa: E402
import program as P  # noqa: E402
import reference as R  # noqa: E402

DATA = BENCH / "tests" / "data"
CELL = "jamba-smoke.doc-qa-smoke"

# A served model that departs from the family's equations: the family file
# with one ModelConfig field flipped, written beside a copy of nothing else.
VARIANT = '''
import family

base = family.load("jamba")
RECIPES, validate, sizes = base.RECIPES, base.validate, base.sizes
layer_shapes, layer, decode_work = (base.layer_shapes, base.layer,
                                    base.decode_work)


def model_config(cfg):
    return dict(base.model_config(cfg), {field}={value})
'''


def test_reference_leaves_are_the_served_tree_at_published_widths():
    """Every leaf of one stacked index (a period of 14 blocks) that the
    reference makes has the path, shape and dtype the program serves for
    jamba2-3b, and the program serves no other: 26 Mamba layers with
    their dt/B/C norms, 2 attention layers of one fused q/k/v leaf."""
    from repro.core import PackedBool
    from repro.models import lm_init
    from repro.serve.engine import pack_weights

    cfg = json.loads((BENCH / "configs" / "jamba2-3b.json").read_text())
    fam = family.of(cfg)
    mc = P.model_config(cfg, fam)
    assert (mc.n_groups, mc.group_size, mc.attn_index) == (2, 14, 7)
    assert not mc.use_rope and mc.ssm_norms and mc.heads_padded() == 32
    tree = jax.eval_shape(
        lambda: pack_weights(lm_init(jax.random.PRNGKey(0), mc)[0]))
    flat = jax.tree_util.tree_flatten_with_path(
        tree["blocks"], is_leaf=lambda x: isinstance(x, PackedBool))[0]
    served = {}
    for p, x in flat:
        arr = x.bits if isinstance(x, PackedBool) else x
        served["['blocks']" + jax.tree_util.keystr(p)] = (
            tuple(arr.shape[1:]), arr.dtype,
            x.k if isinstance(x, PackedBool) else 0)
        assert arr.shape[0] == 2
    want = {p: (tuple(s), jax.numpy.dtype(dt), k)
            for p, (s, dt, k) in R.Arch(cfg, fam).layer_shapes().items()}
    assert served == want
    work = fam.decode_work(cfg)
    assert work["attention"] == {"layers": 2, "heads": 20, "kv_heads": 1,
                                 "head_dim": 128}
    macs = sum(k * n * c for k, n, c in work["projections"])
    # published block weights: 26 x (2*2560*5120 + 5120*192 + 160*5120
    # + 5120*2560) + 2 x (2560*(2560+256) + 2560*2560) + 28 x 3*2560*8192
    assert macs == 26 * (3 * 2560 * 5120 + 5120 * 192 + 160 * 5120) \
        + 2 * (2560 * 2816 + 2560 * 2560) + 28 * 3 * 2560 * 8192


def _run(tmp_path, monkeypatch, variant=None):
    """One smoke run through the paged session, prefix cache on, with a
    snapshot kept every 2 pages so hits are rounded down; returns the
    result and the session's last counters."""
    import repro.serve.prefix_cache as PC

    monkeypatch.setattr(PC, "SNAPSHOT_BYTES_PER_TOKEN", 3000)
    families = family.FAMILIES
    if variant is not None:
        families = tmp_path
        (tmp_path / "jamba.py").write_text(VARIANT.format(
            field=variant[0], value=variant[1]))
    seen = {}
    counters = P.counters
    monkeypatch.setattr(P, "counters",
                        lambda s: seen.update(counters(s)) or dict(seen))
    bench = harness.load_json(DATA / "jamba-bench-smoke.json")
    out = harness.run_cell(bench, CELL, 3, 1.0, False, BENCH.parent,
                           time.perf_counter(), require_chip=False,
                           log=lambda m: None, traffic_dir=DATA,
                           cells_dir=DATA / "cells", families_dir=families)
    return out, seen


# The limit, the smoke configuration's max_logit_gap (float32 served): a
# served token's reference logit lies below the reference's best by a
# float32 rounding (both sides sum exact ±1 weights, in other orders, and
# the tail prefill resumes from snapshots of the same float32 state), so a
# sound run reads about 1e-6; RoPE on the two attention layers, or Mamba
# without its dt/B/C norms, moves logits by percents and flips served
# tokens by gaps of 1e-2 and more.
@pytest.mark.parametrize("variant", [None, ("use_rope", True),
                                     ("ssm_norms", False)],
                         ids=["sound", "rope_on", "norms_off"])
def test_session_serves_the_references_tokens(tmp_path, monkeypatch,
                                              variant):
    out, seen = _run(tmp_path, monkeypatch, variant)
    assert out["failed"] == 0
    gap = out["check"]["max_logit_gap"]
    if variant is None:
        assert out["correct"], gap
        # hits resumed from a snapshot below the match, and said so
        assert seen["partial_hits"] > 0 and seen["resume_gap_tokens"] > 0
        assert seen["ssm_snapshots"] > 0
    else:
        assert not out["correct"] and gap["value"] > gap["limit"], gap
