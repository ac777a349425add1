"""The plain reference against the served per-layer path, on the CPU at a
small size: both compute the same function, so they agree to float32
rounding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references import qwen2
from repro.configs import get_config
from repro.serve import instance


def _small():
    # a name of its own: the served path caches its programs by name
    cfg = get_config("qwen1.5-0.5b").reduced()
    cfg = dataclasses.replace(cfg, name="qwen1.5-bench-reference", rope_theta=1_000_000.0)
    dm = qwen2.Dims(d=cfg.d_model, f=cfg.d_ff, heads=cfg.n_heads,
                    kv_heads=cfg.n_kv_heads, hd=cfg.hd, layers=cfg.n_layers,
                    vocab=cfg.vocab_size, eps=cfg.norm_eps, theta=cfg.rope_theta,
                    init_std=0.02)
    return cfg, dm


def _served_stream(cfg, params, prompt):
    layers = [jax.tree.map(lambda a, i=i: a[i], params["pattern"][0])
              for i in range(cfg.n_layers)]
    S = prompt.shape[0]
    positions = np.broadcast_to(np.arange(S, dtype=np.int32), (1, S))
    x = instance._embed_fn(cfg)(params["embed"], prompt[None])
    for p in layers:
        x, _ = instance._layer_fn(cfg, cfg.pattern[0], "prefill")(
            p, x, positions, None, None)
    tok = instance._head_fn(cfg)(params["embed"], params["final_norm"], x)
    return x[0], int(np.asarray(tok)[0])


def test_reference_matches_served_path():
    cfg, dm = _small()
    params = qwen2.init_params(dm, jax.random.PRNGKey(3))
    prompt = np.random.default_rng(3).integers(0, dm.vocab, 24, dtype=np.int32)
    x, tok = _served_stream(cfg, params, prompt)
    want = qwen2.layers(dm, params, prompt)
    np.testing.assert_allclose(np.asarray(x), np.asarray(want), rtol=2e-5, atol=2e-5)
    logits = qwen2.head(dm, params, want)
    assert tok == int(jnp.argmax(logits[-1]))


def test_init_matches_the_served_tree():
    cfg, dm = _small()
    from repro.models import lm

    got = jax.eval_shape(lambda k: qwen2.init_params(dm, k), jax.random.PRNGKey(0))
    want = lm.abstract_params(cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_flops_hand_count():
    # qwen1.5-0.5b at a 1024-token prompt, counted by hand:
    # per layer 1024*3072 + 1024*1024 + 3*1024*2816 = 12,845,056 weights
    dm = qwen2.Dims(d=1024, f=2816, heads=16, kv_heads=16, hd=64, layers=24,
                    vocab=151936, eps=1e-6, theta=1e6, init_std=0.02)
    S = 1024
    matmul = 24 * 2 * 12_845_056 * S
    attn = 24 * 4 * 1024 * (S * (S + 1) // 2)
    head = 2 * 151936 * 1024
    assert qwen2.flops(dm, S) == matmul + attn + head == 683_261_296_640
