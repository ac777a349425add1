"""Mamba-2 as the published model computes it, on the CPU at the
``mamba2-780m`` preset's reduced size with seeded random weights.

The gated norm is ``mamba_ssm`` ``RMSNormGated(norm_before_gate=False)``:
``rmsnorm(y * silu(z)) * w``, in prefill and in decode.  A node serves a
published fine-tune cold and then warm through ``generate`` (prefill, then
decode through the SSM cache); the residual stream its head receives at
every position gives the logits of a plain float64 full forward over the
prompt and the generated tokens."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch import serve
from repro.models import mamba2
from repro.serve import instance

PROMPT_LEN = 16  # two SSD chunks of the reduced preset's 8
NEW = 4


@pytest.fixture(scope="module")
def cfg():
    return get_config("mamba2-780m").reduced()


def _params(cfg, seed):
    """Seeded random weights, every leaf moved off its initial value (the
    preset's norm weights are ones and its biases zeros)."""
    params = serve.model_params(cfg, seed)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape, a.dtype) for a, k in zip(leaves, keys)])


# ------------------------------------------------------------- plain model
def _rms(v, w, eps):
    return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + eps) * w


def _silu(v):
    return v / (1.0 + np.exp(-v))


def _plain_mixer(cfg, m, u):
    """One Mamba-2 mixer over a whole sequence ``u`` (T, d), the recurrence
    one step at a time, in float64."""
    T = u.shape[0]
    di, N, G, H, P, K = (cfg.d_inner, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_heads,
                         cfg.ssm_head_dim, cfg.conv_kernel)
    zxbcdt = u @ m["in_proj"]
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * G * N], zxbcdt[:, 2 * di + 2 * G * N:]
    padded = np.concatenate([np.zeros((K - 1, xbc.shape[1])), xbc])
    xbc = _silu(sum(padded[k:k + T] * m["conv_w"][k] for k in range(K)) + m["conv_b"])
    xs = xbc[:, :di].reshape(T, H, P)
    Bm = np.repeat(xbc[:, di:di + G * N].reshape(T, G, N), H // G, 1)
    Cm = np.repeat(xbc[:, di + G * N:].reshape(T, G, N), H // G, 1)
    dt = np.log1p(np.exp(dt + m["dt_bias"]))
    A = -np.exp(m["A_log"])
    h = np.zeros((H, P, N))
    ys = []
    for t in range(T):
        h = h * np.exp(dt[t] * A)[:, None, None] + (
            dt[t][:, None, None] * xs[t][:, :, None] * Bm[t][:, None, :])
        ys.append((h * Cm[t][:, None, :]).sum(-1) + xs[t] * m["D"][:, None])
    y = np.stack(ys).reshape(T, di)
    return _rms(y * _silu(z), m["norm_w"], cfg.norm_eps) @ m["out_proj"]


def _plain_stream(cfg, params, tokens):
    """Residual stream before the final norm, (T, d), in float64."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    x = p["embed"]["tok"][tokens]
    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda a: a[i], p["pattern"][0])
        x = x + _plain_mixer(cfg, layer["mamba"], _rms(x, layer["ln1"], cfg.norm_eps))
    return x


def _plain_logits(cfg, params, x):
    """The tied head: final norm, then the token embedding, in float64."""
    x = np.asarray(x, np.float64)
    w = np.asarray(params["embed"]["tok"], np.float64)
    return _rms(x, np.asarray(params["final_norm"], np.float64), cfg.norm_eps) @ w.T


# ------------------------------------------------------------- gated norm
@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_gated_out_is_the_published_gated_norm(cfg, mode, monkeypatch):
    p = jax.tree.map(lambda a: a[0], _params(cfg, 3)["pattern"][0]["mamba"])
    seen = []
    real = mamba2._gated_out

    def spy(cfg_, p_, y, z, dtype):
        out = real(cfg_, p_, y, z, dtype)
        seen.append((np.asarray(y, np.float64), np.asarray(z, np.float64), np.asarray(out)))
        return out

    monkeypatch.setattr(mamba2, "_gated_out", spy)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, PROMPT_LEN, cfg.d_model))
    _, cache = mamba2.mamba_full(cfg, p, x, jnp.float32, return_cache=True)
    if mode == "decode":
        seen.clear()
        mamba2.mamba_decode(cfg, p, x[:, :1], cache, jnp.float32)
    (y, z, out), = seen
    w, proj = np.asarray(p["norm_w"], np.float64), np.asarray(p["out_proj"], np.float64)
    gate_first = _rms(y * _silu(z), w, cfg.norm_eps) @ proj
    norm_first = (_rms(y, w, cfg.norm_eps) * _silu(z)) @ proj
    scale = np.abs(gate_first).max()
    # float32 arithmetic against float64: about 1e-7 of the output's scale
    assert np.abs(out - gate_first).max() <= 1e-5 * scale
    # the other order is a different function, not a rounding away
    assert np.abs(norm_first - gate_first).max() > 0.1 * scale


# ------------------------------------------------------------ served path
@pytest.fixture(scope="module")
def served(cfg, tmp_path_factory):
    """A published fine-tune served cold, then warm, with NEW tokens each;
    the residual streams its head received, per request."""
    params = _params(cfg, 7)
    node = serve.serving_node("fused", serve.image_bytes(params), keep_warm=True)
    streams, runs = [], []
    real = instance._head_fn

    def head_fn(c):
        fn = real(c)

        def call(p_embed, p_norm, x):
            streams[-1].append(np.asarray(x[0], np.float64))
            return fn(p_embed, p_norm, x)

        return call

    prompt = np.random.default_rng(7).integers(0, cfg.vocab_size, (1, PROMPT_LEN),
                                               dtype=np.int32)
    try:
        serve.install_base(node, cfg, params)
        published = serve.publish_functions(
            node, str(tmp_path_factory.mktemp("mamba2")), cfg, params)
        instance._head_fn = head_fn
        for _ in range(2):
            streams.append([])
            runs.append(node.invoke(serve.TUNED_FN, prompt, max_new_tokens=NEW, cfg=cfg))
            # the restore's residual tail lands before the warm request
            assert node.scheduler.drain_residual(60)
    finally:
        instance._head_fn = real
        node.close()
    return published[serve.TUNED_FN], prompt[0], runs, streams


def test_node_serves_cold_then_warm(served):
    _, _, (cold, warm), _ = served
    assert cold.cold and not warm.cold
    assert cold.tokens.shape == warm.tokens.shape == (1, NEW)
    np.testing.assert_array_equal(cold.tokens, warm.tokens)


@pytest.mark.parametrize("kind", ["cold", "warm"])
def test_logits_match_a_plain_full_forward_at_every_position(cfg, served, kind):
    params, prompt, runs, streams = served
    r, got = runs[kind == "warm"], streams[kind == "warm"]
    tokens = r.tokens[0]
    # prefill's stream (all prompt positions), then one per decode step
    assert [s.shape[0] for s in got] == [PROMPT_LEN] + [1] * (NEW - 1)
    seq = np.concatenate([prompt, tokens[:-1]])
    want = _plain_logits(cfg, params, _plain_stream(cfg, params, seq))
    logits = _plain_logits(cfg, params, np.concatenate(got))
    # Float32 layers against float64 on the same weights: the gaps are
    # rounding, about 1e-6 of the logits' scale over two layers; 1e-4
    # leaves room for summation order, while the other gate order, or a
    # decode step that lost the SSM or conv state, moves them by 0.1 or more.
    scale = np.abs(want).max()
    np.testing.assert_allclose(logits, want, rtol=0, atol=1e-4 * scale)
    # each served token is the plain model's first choice at its position
    np.testing.assert_array_equal(tokens, np.argmax(want[PROMPT_LEN - 1:], -1))
