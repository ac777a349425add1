"""Plain reference of the Qwen2 decoder (Qwen1.5 checkpoints use it).

Written from the published description (Hugging Face ``Qwen2ForCausalLM``):
token embedding; per layer a pre-RMSNorm causal multi-head attention with
bias on q, k and v (none on the output), half-rotation RoPE, then a
pre-RMSNorm SwiGLU MLP, each added to the residual; a final RMSNorm and
the (tied) embedding as the output head.  Straight ``jax.numpy``: no
kernels, no cache, one prompt at a time.  It imports nothing of the
system under test.

Weights live in the tree the served program consumes (layers stacked on a
leading axis):

    {"embed": {"tok": (V, d)},
     "pattern": ({"ln1": (L, d),
                  "attn": {"wq", "wk", "wv": (L, d, H*hd), "wo": (L, H*hd, d),
                           "bq", "bk", "bv": (L, H*hd)},
                  "ln2": (L, d),
                  "mlp": {"w_gate", "w_up": (L, d, f), "w_down": (L, f, d)}},),
     "remainder": (),
     "final_norm": (d,)}
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    d: int
    f: int
    heads: int
    kv_heads: int
    hd: int
    layers: int
    vocab: int
    eps: float
    theta: float
    init_std: float


def dims(config: Dict) -> Dims:
    """Sizes from the configuration file's source keys."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    if not config.get("tie_word_embeddings", False):
        raise ValueError("this reference covers tied embeddings only")
    return Dims(
        d=d, f=config["intermediate_size"], heads=h,
        kv_heads=config["num_key_value_heads"], hd=d // h,
        layers=config["num_hidden_layers"], vocab=config["vocab_size"],
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        init_std=float(config["initializer_range"]),
    )


# every layer of the served stack: full causal attention and a dense MLP
LAYER = {"kind": "attn", "window": None, "moe": False, "ffn": True}


def program_fields(dm: Dims) -> Dict:
    """The served program's configuration fields these sizes fix; the
    harness refuses to run a program whose configuration differs."""
    return {
        "d_model": dm.d, "d_ff": dm.f, "n_heads": dm.heads,
        "n_kv_heads": dm.kv_heads, "hd": dm.hd, "n_layers": dm.layers,
        "vocab_size": dm.vocab, "norm_eps": dm.eps, "rope_theta": dm.theta,
        "tie_embeddings": True, "qkv_bias": True, "qk_norm": False,
        "mrope": False, "frontend": None,
    }


def init_params(dm: Dims, key, dtype=jnp.float32):
    """Random weights from ``key``: matrices and biases N(0, init_std),
    norm weights 1 + N(0, init_std).  Traceable: jit it to make the whole
    tree on the device in one call."""
    L, d, f, q, kv = dm.layers, dm.d, dm.f, dm.heads * dm.hd, dm.kv_heads * dm.hd
    shapes = {
        "tok": (dm.vocab, d), "ln1": (L, d), "ln2": (L, d), "final_norm": (d,),
        "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv), "wo": (L, q, d),
        "bq": (L, q), "bk": (L, kv), "bv": (L, kv),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))

    def draw(name):
        w = dm.init_std * jax.random.normal(keys[name], shapes[name], jnp.float32)
        if name in ("ln1", "ln2", "final_norm"):
            w = 1.0 + w
        return w.astype(dtype)

    return {
        "embed": {"tok": draw("tok")},
        "pattern": ({
            "ln1": draw("ln1"),
            "attn": {k: draw(k) for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
            "ln2": draw("ln2"),
            "mlp": {k: draw(k) for k in ("w_gate", "w_up", "w_down")},
        },),
        "remainder": (),
        "final_norm": draw("final_norm"),
    }


def _mm(a, b, precision):
    return jnp.matmul(a, b, precision=precision,
                      preferred_element_type=jnp.float32).astype(a.dtype)


def _rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: (S, heads, hd); rotates the two halves of each head."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, half)
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layers(dm: Dims, params, tokens, dtype=jnp.float32, precision=HIGHEST):
    """Residual stream after the last layer (before the final norm) for
    one prompt ``tokens`` (S,): (S, d) in ``dtype``."""
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    x = p["embed"]["tok"][tokens]
    S = tokens.shape[0]
    rep = dm.heads // dm.kv_heads
    mask = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, w):
        a, m = w["attn"], w["mlp"]
        h = _rmsnorm(x, w["ln1"], dm.eps)
        q = (_mm(h, a["wq"], precision) + a["bq"]).reshape(S, dm.heads, dm.hd)
        k = (_mm(h, a["wk"], precision) + a["bk"]).reshape(S, dm.kv_heads, dm.hd)
        v = (_mm(h, a["wv"], precision) + a["bv"]).reshape(S, dm.kv_heads, dm.hd)
        q, k = _rope(q, dm.theta), _rope(k, dm.theta)
        k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=precision,
                       preferred_element_type=jnp.float32) / jnp.sqrt(dm.hd)
        s = jnp.where(mask, s, -jnp.inf)
        attn = jax.nn.softmax(s, -1).astype(dtype)
        o = jnp.einsum("hqk,khd->qhd", attn, v, precision=precision,
                       preferred_element_type=jnp.float32).astype(dtype)
        x = x + _mm(o.reshape(S, dm.heads * dm.hd), a["wo"], precision)
        h = _rmsnorm(x, w["ln2"], dm.eps)
        g = jax.nn.silu(_mm(h, m["w_gate"], precision).astype(jnp.float32))
        u = _mm(h, m["w_up"], precision)
        x = x + _mm((g.astype(dtype) * u), m["w_down"], precision)
        return x, None

    x, _ = jax.lax.scan(layer, x, p["pattern"][0])
    return x


def head(dm: Dims, params, x, precision=HIGHEST):
    """Logits (S, V) in float32 from a residual stream ``x`` (S, d): the
    final norm, then the tied embedding."""
    dtype = x.dtype
    h = _rmsnorm(x, params["final_norm"].astype(dtype), dm.eps)
    return jnp.matmul(h, params["embed"]["tok"].astype(dtype).T,
                      precision=precision, preferred_element_type=jnp.float32)


def flops(dm: Dims, prompt_len: int) -> float:
    """Model FLOPs of one prefill whose head scores the last position only
    (what a first token needs): 2 per multiply-add of every weight matrix
    per token, causal attention scores and values (S(S+1)/2 pairs per
    head), and the tied head once."""
    S = prompt_len
    q, kv = dm.heads * dm.hd, dm.kv_heads * dm.hd
    per_layer = dm.d * (q + 2 * kv) + q * dm.d + 3 * dm.d * dm.f
    attn = 2 * 2 * q * S * (S + 1) // 2
    return float(dm.layers * (2 * per_layer * S + attn) + 2 * dm.vocab * dm.d)
