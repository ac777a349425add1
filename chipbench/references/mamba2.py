"""Plain reference of the Mamba-2 language model (arXiv:2405.21060;
``state-spaces/mamba2-*`` checkpoints, ``mamba_ssm`` ``MambaLMHeadModel``).

Per layer: ``x + mixer(RMSNorm(x))``.  The mixer projects to (z, xBC, dt),
runs a causal depthwise convolution with bias over xBC and a SiLU, takes
``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, and runs the SSD
recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``,
``y_t = C_t h_t + D x_t`` here as a plain scan over time.  The gated norm
of the published model multiplies by ``silu(z)`` before the RMSNorm
(``norm_before_gate=False``); ``gate_first=False`` gives the other order.
Then the output projection, a final RMSNorm and the tied embedding.  It
imports nothing of the system under test.

Weights live in the tree the served program consumes (layers stacked):
``{"embed": {"tok"}, "pattern": ({"ln1", "mamba": {"in_proj", "conv_w",
"conv_b", "A_log", "D", "dt_bias", "norm_w", "out_proj"}},), "remainder":
(), "final_norm"}``.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    d: int
    layers: int
    vocab: int
    state: int
    head_dim: int
    inner: int
    groups: int
    conv: int
    eps: float
    init_std: float

    @property
    def heads(self) -> int:
        return self.inner // self.head_dim


def dims(config: Dict) -> Dims:
    """Sizes from the configuration file's source keys (mamba_ssm names)."""
    ssm = config["ssm_cfg"]
    pad = config.get("pad_vocab_size_multiple", 1)
    vocab = -(-config["vocab_size"] // pad) * pad
    return Dims(
        d=config["d_model"], layers=config["n_layer"], vocab=vocab,
        state=ssm["d_state"], head_dim=ssm["headdim"],
        inner=ssm["expand"] * config["d_model"], groups=ssm["ngroups"],
        conv=ssm["d_conv"], eps=float(config["norm_epsilon"]),
        init_std=float(config["initializer_range"]),
    )


def init_params(dm: Dims, key, dtype=jnp.float32):
    """Random weights: matrices N(0, init_std), conv weights and biases
    N(0, 0.1), A in [1, 16], dt_bias the inverse softplus of dt drawn
    log-uniform in [0.001, 0.1] (the published initialisation's ranges),
    D and norm weights 1 + N(0, init_std)."""
    L, d, di, G, N, H = dm.layers, dm.d, dm.inner, dm.groups, dm.state, dm.heads
    conv_dim = di + 2 * G * N
    k = dict(zip(["tok", "ln1", "fn", "in", "cw", "cb", "a", "dd", "dt", "nw", "out"],
                 jax.random.split(key, 11)))
    n = jax.random.normal
    dt = jnp.exp(jax.random.uniform(k["dt"], (L, H), minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    tree = {
        "embed": {"tok": dm.init_std * n(k["tok"], (dm.vocab, d))},
        "pattern": ({
            "ln1": 1.0 + dm.init_std * n(k["ln1"], (L, d)),
            "mamba": {
                "in_proj": dm.init_std * n(k["in"], (L, d, 2 * di + 2 * G * N + H)),
                "conv_w": 0.1 * n(k["cw"], (L, dm.conv, conv_dim)),
                "conv_b": 0.1 * n(k["cb"], (L, conv_dim)),
                "A_log": jnp.log(jax.random.uniform(k["a"], (L, H), minval=1.0, maxval=16.0)),
                "D": 1.0 + dm.init_std * n(k["dd"], (L, H)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm_w": 1.0 + dm.init_std * n(k["nw"], (L, di)),
                "out_proj": dm.init_std * n(k["out"], (L, di, d)),
            },
        },),
        "remainder": (),
        "final_norm": 1.0 + dm.init_std * n(k["fn"], (d,)),
    }
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def _rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * w.astype(jnp.float32)).astype(x.dtype)


def layers(dm: Dims, params, tokens, dtype=jnp.float32, precision=HIGHEST,
           gate_first: bool = True):
    """Residual stream after the last layer (before the final norm) for
    one prompt ``tokens`` (S,)."""
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    x = p["embed"]["tok"][tokens]
    S = tokens.shape[0]
    di, G, N, H, P, K = dm.inner, dm.groups, dm.state, dm.heads, dm.head_dim, dm.conv

    def mixer(u, m):
        zxbcdt = jnp.matmul(u, m["in_proj"], precision=precision)
        z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * G * N], zxbcdt[:, 2 * di + 2 * G * N:]
        padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), xbc.dtype), xbc])
        conv = sum(padded[i:i + S] * m["conv_w"][i] for i in range(K)) + m["conv_b"]
        xbc = jax.nn.silu(conv.astype(jnp.float32))
        xs = xbc[:, :di].reshape(S, H, P)
        Bm = jnp.repeat(xbc[:, di:di + G * N].reshape(S, G, N), H // G, 1)
        Cm = jnp.repeat(xbc[:, di + G * N:].reshape(S, G, N), H // G, 1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + m["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(m["A_log"].astype(jnp.float32))

        def step(h, inp):
            x_t, b_t, c_t, dt_t = inp
            h = h * jnp.exp(dt_t * A)[:, None, None] + (dt_t[:, None, None]
                                                        * x_t[:, :, None] * b_t[:, None, :])
            return h, jnp.einsum("hpn,hn->hp", h, c_t, precision=precision)

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (xs, Bm, Cm, dt))
        y = (y + xs * m["D"].astype(jnp.float32)[:, None]).reshape(S, di).astype(dtype)
        gate = jax.nn.silu(z.astype(jnp.float32)).astype(dtype)
        if gate_first:
            y = _rmsnorm(y * gate, m["norm_w"], dm.eps)
        else:
            y = _rmsnorm(y, m["norm_w"], dm.eps) * gate
        return jnp.matmul(y, m["out_proj"], precision=precision)

    def layer(x, w):
        return x + mixer(_rmsnorm(x, w["ln1"], dm.eps), w["mamba"]).astype(dtype), None

    x, _ = jax.lax.scan(layer, x, p["pattern"][0])
    return x


def head(dm: Dims, params, x, precision=HIGHEST):
    """Logits (S, V) in float32: the final norm, then the tied embedding."""
    dtype = x.dtype
    h = _rmsnorm(x, params["final_norm"].astype(dtype), dm.eps)
    return jnp.matmul(h, params["embed"]["tok"].astype(dtype).T,
                      precision=precision, preferred_element_type=jnp.float32)
