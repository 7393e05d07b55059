"""The family seam: a model family is one file, ``families/<family>.py``,
found by the name a configuration gives; the dense and ssm families moved
there serve and compute exactly what they did before; packed leaves may
carry an experts axis. CPU, smoke size."""
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import family  # noqa: E402
import harness  # noqa: E402
import program as P  # noqa: E402
import reference as R  # noqa: E402
import weights as W  # noqa: E402

DATA = BENCH / "tests" / "data"
GOLDEN = json.loads((DATA / "golden-smoke.json").read_text())["configs"]

# A family the benchmark does not have: dense GQA without QKV bias, which
# the program packs as one fused ``wqkv`` leaf ([q | k | v] columns), with
# a weight recipe of its own for the norm scales.
FUSED = '''
import math

import jax
import jax.numpy as jnp

import family
import reference as R

dense = family.load("dense")
RECIPES = {"scale": lambda k, s: 0.05 * jax.random.normal(k, s, jnp.float32)}
validate, sizes, decode_work = dense.validate, dense.sizes, dense.decode_work


def model_config(cfg):
    return dict(dense.model_config(cfg), qkv_bias=False)


def layer_shapes(arch):
    out = {p: s for p, s in dense.layer_shapes(arch).items()
           if not any(n in p for n in ("['wq']", "['wk']", "['wv']"))}
    cols = (arch.hp + 2 * arch.kv) * arch.hd
    out["['blocks']['b0']['attn']['wqkv']['w']"] = (
        (arch.d // 32, cols), jnp.uint32, arch.d)
    return out


def layer(arch, w, h, pos, mode):
    d, hd, hp, kv = arch.d, arch.hd, arch.hp, arch.kv
    S = h.shape[0]
    x = R._rms(h, w["norm1.scale"])
    qkv = R._proj(x, w["wqkv.w"], d, mode)
    q = qkv[:, :hp * hd].reshape(S, hp, hd)
    k = qkv[:, hp * hd:(hp + kv) * hd].reshape(S, kv, hd)
    v = qkv[:, (hp + kv) * hd:].reshape(S, kv, hd)
    q = R._rope(q, pos, arch.theta)[:, :arch.heads]
    k = R._rope(k, pos, arch.theta)
    group = jnp.arange(arch.heads) // (hp // kv)
    o = dense.attend(arch, q, k[:, group], v[:, group], pos, mode)
    wo = R.unpack_pm1(w["wo.w"], hp * hd)[:arch.heads * hd]
    h = h + R._mm(o, wo, mode) / math.sqrt(hp * hd)
    x = R._rms(h, w["norm2.scale"])
    gu = R._proj(x, w["wgu.w"], d, mode)
    g, u = gu[:, :arch.ff], gu[:, arch.ff:]
    a = jnp.where(g - w["tau"] >= 0, 1.0, -1.0)
    return h + R._proj(a * u, w["wd.w"], arch.ff, mode)
'''


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()
                          ).hexdigest()


def program_logits(cfg, fam, params, prompt, served):
    """Logits of the prompt's last position and of each decode step,
    through the program's own prefill and cached decode."""
    from repro.models import cache_init, lm_decode_step, lm_prefill

    mc = P.model_config(cfg, fam)
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


def test_new_family_is_one_file(tmp_path):
    """A family written into another directory is found by its name, and
    a smoke configuration of it resolves, makes its weights, and runs its
    reference, which agrees with the program; no file of bench/ changes."""
    (tmp_path / "dense_fused.py").write_text(FUSED)
    cfg = json.loads((DATA / "dense-smoke.json").read_text())
    cfg["name"] = "fused-smoke"
    cfg["program"] = dict(cfg["program"], family="dense_fused",
                          qkv_bias=False,
                          overrides=dict(cfg["program"]["overrides"],
                                         dtype="float32"))
    (tmp_path / "fused-smoke.json").write_text(json.dumps(cfg))
    bench = harness.load_json(DATA / "bench-smoke.json")
    bench["configs"] = [{"name": "fused-smoke", "source": "test",
                         "file": "fused-smoke.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "fused-smoke.chat-prefix-smoke",
                           "config": "fused-smoke",
                           "traffic": "chat-prefix-smoke", "chips": 1,
                           "why": "test"}]
    bench["end_to_end"] = [m for m in bench["end_to_end"]
                           if "workloads" not in m]
    spec = harness.resolve(bench, "fused-smoke.chat-prefix-smoke", tmp_path,
                           traffic_dir=DATA, cells_dir=tmp_path,
                           families_dir=tmp_path)
    fam = spec["family"]
    assert fam is family.load("dense_fused", tmp_path)
    assert not (BENCH / "families" / "dense_fused.py").exists()
    cfg = spec["config"]
    mc = P.model_config(cfg, fam)
    assert not mc.qkv_bias
    params = P.make_params(mc, 7, fam)
    wqkv = params["blocks"]["b0"]["attn"]["wqkv"]["w"]
    assert wqkv.bits.shape == (2, 2, (16 + 2 * 2) * 16)
    scale = params["blocks"]["b0"]["norm1"]["scale"]
    assert 0.02 < float(jnp.std(scale)) < 0.1      # the family's recipe
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg["vocab_size"], 23)
    served = rng.integers(0, cfg["vocab_size"], 9)
    got = program_logits(cfg, fam, params, prompt, served)
    arch = R.Arch(cfg, fam)
    h = R.final_hidden(arch, 7, [list(prompt) + list(served[:-1])])[0]
    want = np.asarray(R._logits(arch, R._head(arch, W.seed_key(7)),
                                h[len(prompt) - 1:], "f32"))
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err <= 1e-4, err      # float32 on both sides, as the dense test


def test_unknown_family_is_an_error(tmp_path):
    with pytest.raises(ValueError, match="no family"):
        family.load("no_such_family", tmp_path)


def _smoke(name):
    cfg = json.loads((DATA / f"{name}.json").read_text())
    return cfg, GOLDEN[name]


@pytest.mark.parametrize("name", ["dense-smoke", "ssm-smoke"])
def test_served_words_are_the_parents(name):
    """Every leaf the program serves is byte for byte what the harness
    made before the families moved out of program.py."""
    from repro.core import PackedBool

    cfg, gold = _smoke(name)
    params = P.make_params(P.model_config(cfg), gold["seed"],
                           family.of(cfg))
    flat = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, PackedBool))[0]
    got = {jax.tree_util.keystr(p): _sha(x.bits if isinstance(x, PackedBool)
                                         else x) for p, x in flat}
    assert got == gold["leaves"]


@pytest.mark.parametrize("name", ["dense-smoke", "ssm-smoke"])
def test_served_streams_are_the_parents(name):
    cfg, gold = _smoke(name)
    fam = family.of(cfg)
    mc = P.model_config(cfg, fam)
    eng, sess = P.open_session(mc, P.make_params(mc, gold["seed"], fam),
                               cfg["serve"])
    hs = [sess.submit(np.asarray(p), P.sampling(gold["max_tokens"]))
          for p in gold["prompts"]]
    sess.run_until_idle()
    assert [list(map(int, h.tokens_so_far())) for h in hs] == \
        gold["streams"]
    sess.close()


@pytest.mark.parametrize("name", ["dense-smoke", "ssm-smoke"])
def test_reference_logits_are_the_parents(name):
    cfg, gold = _smoke(name)
    arch = R.Arch(cfg)
    seed, pad = gold["seed"], cfg["serve"]["max_len"]
    seq = gold["prompts"][3] + gold["streams"][3][:-1]
    for mode in ("f32", "fp8"):
        h = R.final_hidden(arch, seed, [seq], mode, pad_to=pad)[0]
        lg = R._logits(arch, R._head(arch, W.seed_key(seed)), h[:len(seq)],
                       mode)
        assert _sha(lg) == gold["logits_sha256"][mode], mode
    reqs = [(np.asarray(p), np.asarray(t))
            for p, t in zip(gold["prompts"], gold["streams"])]
    gaps = R.served_gaps(arch, seed, reqs, pad_to=pad)
    ctl = R.served_gaps(arch, seed, reqs, control=True, pad_to=pad)
    assert [list(map(float, g)) for g in gaps] == gold["gaps"]
    assert [list(map(float, g)) for g in ctl] == gold["control_gaps"]


def test_packed_leaf_with_an_experts_axis(monkeypatch):
    """``make_params`` makes a packed leaf of shape (stack, experts, Kw,
    N): each index of the stack as ``layer_leaf`` makes it, bits past the
    fan-in zero in every expert."""
    import repro.serve.engine as E
    from repro.core import PackedBool

    path = "['blocks']['b0']['moe']['experts']['w']"
    orig = E.pack_weights

    def with_experts(params, tp=1):
        out = orig(params, tp)
        out["blocks"]["b0"]["moe"] = {"experts": {"w": PackedBool(
            jnp.zeros((2, 3, 2, 24), jnp.uint32), 40)}}
        return out

    monkeypatch.setattr(E, "pack_weights", with_experts)
    cfg = json.loads((DATA / "dense-smoke.json").read_text())
    mc = P.model_config(cfg)
    params = P.make_params(mc, 2**33 + 1, family.of(cfg))
    got = params["blocks"]["b0"]["moe"]["experts"]["w"]
    assert got.bits.shape == (2, 3, 2, 24) and got.k == 40
    key = W.seed_key(2**33 + 1)
    for layer in range(2):
        np.testing.assert_array_equal(
            got.bits[layer], W.layer_leaf(key, path, layer, (3, 2, 24),
                                          jnp.uint32, 64, k=40))
    assert int(jnp.max(got.bits[:, :, 1] >> 8)) == 0    # bits 40..63
    assert len({_sha(got.bits[0, e]) for e in range(3)}) == 3
    assert R.unpack_pm1(got.bits[0], 40).shape == (3, 40, 24)


def test_grouped_family_names_each_blocks_leaves():
    """A family that stacks a group of blocks per index names the leaves
    of b1.. by their block; b0's keep the names dense and ssm use."""
    assert R._short("['blocks']['b0']['attn']['wq']['w']") == "wq.w"
    assert R._short("['blocks']['b3']['attn']['wq']['w']") == "b3.wq.w"
    assert R._short("['blocks']['b1']['mamba']['A_log']") == "b1.A_log"
    assert R._short("['final_norm']['scale']") == "final_norm.scale"
