"""Serving engine: publish -> cold start -> warm; the paper's baseline
restorers, run standalone, must rebuild the same tree and tokens as a
spice restore."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import BaseImage, SpiceRestorer, baselines
from repro.core.treeutil import flatten_state
from repro.models import lm
from repro.serve.engine import (
    ServerlessNode,
    generate,
    layer_sequence,
    layerwise_state,
)

ARCH = "qwen1.5-0.5b"


@pytest.fixture(scope="module")
def node_with_fn(tmp_path_factory):
    d = tmp_path_factory.mktemp("fns")
    cfg = get_config(ARCH).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    node = ServerlessNode()
    node.publish("f1", cfg, params, str(d), warm_ttl_s=60.0,
                 formats=("jif", "criu", "monolith"),
                 extra_state={"opt_m": np.ones((1 << 16,), np.float32)})
    return node, cfg


PROMPT = np.array([[5, 6, 7, 8, 9, 10]], dtype=np.int32)


BASELINES = {  # restore of a function's image in the baseline's format
    "criu_star": lambda jif: baselines.criu_star_restore(
        jif.replace(".jif", ".criu"))[0],
    "reap_star": lambda jif: baselines.reap_star_restore(
        jif.replace(".jif", ".mono"))[0],
    "faasnap_star": lambda jif: baselines.FaasnapAsyncRestorer(
        jif.replace(".jif", ".mono")).state(),
}


@pytest.mark.parametrize("baseline", sorted(BASELINES))
def test_all_modes_agree(node_with_fn, baseline):
    node, cfg = node_with_fn
    jif = node.registry.get("f1").jif_path
    spice, _, _, _ = SpiceRestorer(node_cache=node.node_cache).restore(jif)
    want = {n: a for n, a in flatten_state(spice)[0]
            if not n.startswith("__extra__/")}
    got = BASELINES[baseline](jif)
    leaves = flatten_state(got)[0]
    assert sorted(n for n, _ in leaves) == sorted(want)
    for name, arr in leaves:
        np.testing.assert_array_equal(np.asarray(arr), want[name],
                                      err_msg=f"{baseline}: {name}")
    node.evict()
    r = node.invoke("f1", PROMPT, max_new_tokens=6, cfg=cfg)
    assert r.cold
    toks, _ = generate(cfg, None, got, PROMPT, 6)
    np.testing.assert_array_equal(toks, r.tokens, err_msg=baseline)
    assert r.tokens.shape == (1, 6)


def test_warm_path_matches_cold(node_with_fn):
    node, cfg = node_with_fn
    node.evict()
    cold = node.invoke("f1", PROMPT, max_new_tokens=4, cfg=cfg)
    warm = node.invoke("f1", PROMPT, max_new_tokens=4, cfg=cfg)
    assert cold.cold and not warm.cold
    np.testing.assert_array_equal(cold.tokens, warm.tokens)
    assert warm.total_s <= cold.total_s + 1.0


def test_generation_matches_lm_forward(node_with_fn):
    """Engine layerwise generation == monolithic lm.prefill/decode path."""
    node, cfg = node_with_fn
    node.evict()
    r = node.invoke("f1", PROMPT, max_new_tokens=3, cfg=cfg)

    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    logits, caches, _ = lm.prefill(
        cfg, params, {"tokens": jnp.asarray(PROMPT)}, compute_dtype=jnp.float32
    )
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = PROMPT.shape[1]
    for _ in range(2):
        logits, caches, _ = lm.decode_step(
            cfg, params, {"tokens": jnp.asarray([[toks[-1]]], jnp.int32)},
            caches, jnp.int32(pos), compute_dtype=jnp.float32,
        )
        toks.append(int(jnp.argmax(logits[0, -1])))
        pos += 1
    np.testing.assert_array_equal(r.tokens[0], np.asarray(toks))


def test_layerwise_state_roundtrip(node_with_fn):
    node, cfg = node_with_fn
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    state = layerwise_state(cfg, params)
    assert len(state["layers"]) == cfg.n_layers
    np.testing.assert_array_equal(
        state["layers"][0]["attn"]["wq"], np.asarray(params["pattern"][0]["attn"]["wq"][0])
    )


def test_base_image_dedup_across_finetunes(tmp_path):
    """Two functions sharing a base: the second one's JIF is mostly BASE."""
    cfg = get_config(ARCH).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    node = ServerlessNode()
    base_state = layerwise_state(cfg, params)
    node.node_cache.put(BaseImage.from_state("base-lm", base_state))

    # fine-tune: perturb only the first layer
    ft = jax.tree.map(lambda a: a, params)
    ft["pattern"][0]["attn"]["wq"] = ft["pattern"][0]["attn"]["wq"] + 0.5
    from repro.core.snapshot import snapshot as jif_snapshot

    stats = jif_snapshot(
        layerwise_state(cfg, ft), str(tmp_path / "ft.jif"),
        base=node.node_cache.get("base-lm"),
    )
    assert stats.base_bytes > 0.5 * stats.total_bytes
    assert stats.private_bytes < 0.5 * stats.total_bytes
