"""The Mamba-2 reference against the served per-layer path, on the CPU at
a small size.  The published model gates before its norm; the served
layer is compared in both orders, and must match the reference in one."""
import dataclasses

import jax
import numpy as np

from chipbench.references import mamba2
from repro.configs import get_config
from repro.serve import instance


def _small():
    cfg = get_config("mamba2-780m").reduced()
    cfg = dataclasses.replace(cfg, name="mamba2-bench-small", norm_eps=1e-5)
    dm = mamba2.Dims(d=cfg.d_model, layers=cfg.n_layers, vocab=cfg.vocab_size,
                     state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
                     inner=cfg.d_inner, groups=cfg.ssm_groups, conv=cfg.conv_kernel,
                     eps=cfg.norm_eps, init_std=0.02)
    return cfg, dm


def _served(cfg, params, prompt):
    S = prompt.shape[0]
    positions = np.broadcast_to(np.arange(S, dtype=np.int32), (1, S))
    x = instance._embed_fn(cfg)(params["embed"], prompt[None])
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda a, i=i: a[i], params["pattern"][0])
        x, _ = instance._layer_fn(cfg, cfg.pattern[0], "prefill")(p, x, positions, None, None)
    return np.asarray(x[0])


def _gaps(seed):
    cfg, dm = _small()
    params = mamba2.init_params(dm, jax.random.PRNGKey(seed))
    prompt = np.random.default_rng(seed).integers(0, dm.vocab, 32, dtype=np.int32)
    got = _served(cfg, params, prompt)
    scale = np.abs(got).max()
    return {order: float(np.abs(got - np.asarray(mamba2.layers(
        dm, params, prompt, gate_first=order))).max() / scale)
        for order in (True, False)}


def test_served_mamba2_layer_matches_the_reference_in_one_gate_order():
    for seed in (1, 2, 3):
        gaps = _gaps(seed)
        assert min(gaps.values()) < 1e-5, gaps


def test_dims_from_the_published_keys():
    cfg = {"d_model": 1536, "n_layer": 48, "vocab_size": 50277,
           "pad_vocab_size_multiple": 16, "norm_epsilon": 1e-5,
           "initializer_range": 0.02,
           "ssm_cfg": {"d_state": 128, "headdim": 64, "expand": 2,
                       "ngroups": 1, "d_conv": 4}}
    dm = mamba2.dims(cfg)
    assert (dm.vocab, dm.inner, dm.heads) == (50288, 3072, 48)
