"""The benchmark's weights: made layer by layer they equal the stacked
tree, and the packed words it makes serve the same streams as the int8
tree they encode, packed all at once by the program. CPU, smoke size."""
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
import weights as W  # noqa: E402

DATA = BENCH / "tests" / "data"


def load(name):
    return json.loads((DATA / f"{name}.json").read_text())


def test_layer_by_layer_equals_stacked():
    key = W.seed_key(2**33 + 5)
    path = "['blocks']['b0']['ffn']['wgu']['w']"
    stacked = W.make_stacked(key, path, 3, (4, 16), jnp.uint32, 64, k=128)
    for layer in range(3):
        one = W.layer_leaf(key, path, layer, (4, 16), jnp.uint32, 64, k=128)
        np.testing.assert_array_equal(stacked[layer], one)
    np.testing.assert_array_equal(stacked[:, :, :8], stacked[:, :, 8:])


def test_seeds_past_32_bits_differ():
    a = W.seed_key(5)
    b = W.seed_key(2**32 + 5)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))


def test_padding_bits_are_zero():
    words = W.packed_bits(jax.random.PRNGKey(0), (2, 8), 40)
    assert int(jnp.max(words[1] >> 8)) == 0      # bits 40..63 of each column
    assert int(jnp.max(words[0])) > 2**31


def _int8_tree(mc, packed):
    """The int8 ±1 tree lm_init's layout holds, decoded from the packed
    words, with the fused gate|up split back into its two halves."""
    from repro.core import PackedBool
    from repro.models import lm_init

    flat = {jax.tree_util.keystr(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(
                packed, is_leaf=lambda x: isinstance(x, PackedBool))[0]}
    shapes = jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), mc)[0])

    def fill(path, leaf):
        p = jax.tree_util.keystr(path)
        if leaf.dtype != jnp.int8:
            return flat[p]
        src = p.replace("['wg']", "['wgu']").replace("['wu']", "['wgu']")
        w = flat[src]
        pm1 = jnp.stack([R.unpack_pm1(b, w.k) for b in w.bits])
        if src != p:
            half = pm1.shape[-1] // 2
            pm1 = pm1[..., :half] if "['wg']" in p else pm1[..., half:]
        return pm1.astype(jnp.int8)

    return jax.tree_util.tree_map_with_path(fill, shapes)


@pytest.mark.parametrize("name", ["dense-smoke", "ssm-smoke"])
def test_made_words_serve_like_packing_all_at_once(name):
    from repro.serve import ServeEngine

    cfg = load(name)
    mc = P.model_config(cfg)
    made = P.make_params(mc, 11)
    serve = dict(cfg["serve"], prefix_cache=False)
    eng_a, sess_a = P.open_session(mc, made, serve)
    eng_b = ServeEngine(mc, _int8_tree(mc, made), max_len=serve["max_len"],
                        packed=True)
    sess_b = eng_b.session(lanes=serve["lanes"], page_size=serve["page_size"],
                           segment=serve["segment"], buckets=serve["buckets"])
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], n) for n in (5, 17, 30)]
    streams = []
    for sess in (sess_a, sess_b):
        hs = [sess.submit(p, P.sampling(8)) for p in prompts]
        sess.run_until_idle()
        streams.append([h.tokens_so_far() for h in hs])
        sess.close()
    assert streams[0] == streams[1]
