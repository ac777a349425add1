"""Mamba2 / SSD (state-space duality) blocks: chunked scan + O(1) decode.

Pure-jnp SSD implementation (chunk-parallel form of arXiv:2405.21060 listing
1); the Pallas ``ssd_scan`` kernel is the TPU deployment path validated
against this module.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import rmsnorm
from repro.sharding.partition import ParamSpec, constrain


def mamba_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    di, N, H, G, K = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_groups, cfg.conv_kernel
    conv_dim = di + 2 * G * N
    zdim = 2 * di + 2 * G * N + H
    if cfg.mamba_split_proj:
        # shard-aligned streams: no slicing of a sharded fused dim
        return {
            "w_z": ParamSpec((d, di), ("fsdp", "model"), init="fanin"),
            "w_x": ParamSpec((d, di), ("fsdp", "model"), init="fanin"),
            "w_B": ParamSpec((d, G * N), ("fsdp", "model"), init="fanin"),
            "w_C": ParamSpec((d, G * N), ("fsdp", "model"), init="fanin"),
            "w_dt": ParamSpec((d, H), ("fsdp", "model"), init="fanin"),
            "conv_x_w": ParamSpec((K, di), (None, "model"), init="normal"),
            "conv_x_b": ParamSpec((di,), ("model",), init="zeros"),
            "conv_B_w": ParamSpec((K, G * N), (None, "model"), init="normal"),
            "conv_B_b": ParamSpec((G * N,), ("model",), init="zeros"),
            "conv_C_w": ParamSpec((K, G * N), (None, "model"), init="normal"),
            "conv_C_b": ParamSpec((G * N,), ("model",), init="zeros"),
            "A_log": ParamSpec(
                (H,), (None,), dtype=jnp.float32,
                init_fn=lambda key, shape, dtype: jnp.log(
                    jax.random.uniform(key, shape, minval=1.0, maxval=16.0)
                ).astype(dtype),
            ),
            "D": ParamSpec((H,), (None,), init="ones", dtype=jnp.float32),
            "dt_bias": ParamSpec((H,), (None,), init="zeros", dtype=jnp.float32),
            "norm_w": ParamSpec((di,), ("model",), init="ones", dtype=jnp.float32),
            "out_proj": ParamSpec((di, d), ("model", "fsdp"), init="fanin"),
        }
    return {
        "in_proj": ParamSpec((d, zdim), ("fsdp", "model"), init="fanin"),
        "conv_w": ParamSpec((K, conv_dim), (None, "model"), init="normal"),
        "conv_b": ParamSpec((conv_dim,), ("model",), init="zeros"),
        "A_log": ParamSpec(
            (H,), (None,), dtype=jnp.float32,
            init_fn=lambda key, shape, dtype: jnp.log(
                jax.random.uniform(key, shape, minval=1.0, maxval=16.0)
            ).astype(dtype),
        ),
        "D": ParamSpec((H,), (None,), init="ones", dtype=jnp.float32),
        "dt_bias": ParamSpec((H,), (None,), init="zeros", dtype=jnp.float32),
        "norm_w": ParamSpec((di,), ("model",), init="ones", dtype=jnp.float32),
        "out_proj": ParamSpec((di, d), ("model", "fsdp"), init="fanin"),
    }


def segsum(x: jax.Array) -> jax.Array:
    """x: (..., T) -> (..., T, T) with out[i,j] = sum_{j < t <= i} x[t]; -inf above diag."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd(
    x: jax.Array,  # (b, s, h, p) — inputs already scaled by dt
    a: jax.Array,  # (b, s, h) — dt * A (negative)
    Bm: jax.Array,  # (b, s, g, n)
    Cm: jax.Array,  # (b, s, g, n)
    chunk: int,
    init_state: Optional[jax.Array] = None,  # (b, h, p, n)
) -> Tuple[jax.Array, jax.Array]:
    b, s, h, pdim = x.shape
    g, n = Bm.shape[-2:]
    chunk = min(chunk, s)
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    c = s // chunk
    rep = h // g

    xr = x.reshape(b, c, chunk, h, pdim)
    ar = a.reshape(b, c, chunk, h).transpose(0, 3, 1, 2).astype(jnp.float32)  # (b,h,c,l)
    Bh = jnp.repeat(Bm.reshape(b, c, chunk, g, n), rep, axis=3)  # (b,c,l,h,n)
    Ch = jnp.repeat(Cm.reshape(b, c, chunk, g, n), rep, axis=3)

    a_cs = jnp.cumsum(ar, axis=-1)  # (b,h,c,l)

    # 1. intra-chunk (diagonal) term
    L = jnp.exp(segsum(ar)).astype(x.dtype)  # (b,h,c,l,l)
    Y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", Ch, Bh, L, xr)

    # 2. per-chunk final states
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs).astype(x.dtype)  # (b,h,c,l)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bh, decay_states, xr)

    # 3. inter-chunk recurrence
    if init_state is None:
        init_state = jnp.zeros((b, h, pdim, n), states.dtype)
    states = jnp.concatenate([init_state[:, None], states], axis=1)  # (b,c+1,h,p,n)
    chunk_sum = a_cs[..., -1]  # (b,h,c)
    padded = jnp.pad(chunk_sum, ((0, 0), (0, 0), (1, 0)))
    decay_chunk = jnp.exp(segsum(padded)).astype(x.dtype)  # (b,h,c+1,c+1)
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)
    states_in, final_state = new_states[:, :-1], new_states[:, -1]

    # 4. state -> output
    state_decay = jnp.exp(a_cs).astype(x.dtype)  # (b,h,c,l)
    Y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Ch, states_in, state_decay)

    return (Y_diag + Y_off).reshape(b, s, h, pdim), final_state


def _split_zxbcdt(cfg: ModelConfig, zxbcdt: jax.Array):
    di, N, G, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_heads
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di : di + di + 2 * G * N]
    dt = zxbcdt[..., di + di + 2 * G * N :]
    return z, xBC, dt


def _causal_conv(xs, w, b, K, S, compute_dtype):
    pad = jnp.pad(xs, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + S, :] * w[i].astype(compute_dtype) for i in range(K))
    out = out + b.astype(compute_dtype)
    return jax.nn.silu(out.astype(jnp.float32)).astype(compute_dtype), pad[:, -(K - 1) :, :]


def _gated_out(cfg, p, y, z, compute_dtype):
    # RMSNorm(y * silu(z)), the published gated norm (mamba_ssm
    # RMSNormGated, norm_before_gate=False), then output projection
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(compute_dtype),
                p["norm_w"], cfg.norm_eps)
    return jnp.einsum("bsi,id->bsd", y, p["out_proj"].astype(compute_dtype))


def mamba_full(
    cfg: ModelConfig,
    p: Dict,
    x: jax.Array,  # (B, S, d)
    compute_dtype,
    return_cache: bool = False,
) -> Tuple[jax.Array, Optional[Dict]]:
    B, S, _ = x.shape
    di, N, G, H, K = cfg.d_inner, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_heads, cfg.conv_kernel
    P = cfg.ssm_head_dim

    if cfg.mamba_split_proj:
        z = jnp.einsum("bsd,dz->bsz", x, p["w_z"].astype(compute_dtype))
        xs = jnp.einsum("bsd,dz->bsz", x, p["w_x"].astype(compute_dtype))
        Bs = jnp.einsum("bsd,dz->bsz", x, p["w_B"].astype(compute_dtype))
        Cs = jnp.einsum("bsd,dz->bsz", x, p["w_C"].astype(compute_dtype))
        dt = jnp.einsum("bsd,dz->bsz", x, p["w_dt"].astype(compute_dtype))
        xs, pad_x = _causal_conv(xs, p["conv_x_w"], p["conv_x_b"], K, S, compute_dtype)
        Bs, pad_B = _causal_conv(Bs, p["conv_B_w"], p["conv_B_b"], K, S, compute_dtype)
        Cs, pad_C = _causal_conv(Cs, p["conv_C_w"], p["conv_C_b"], K, S, compute_dtype)
        x_in = constrain(xs.reshape(B, S, H, P), "batch", None, "heads", None)
        Bm = Bs.reshape(B, S, G, N)
        Cm = Cs.reshape(B, S, G, N)
    else:
        zxbcdt = jnp.einsum("bsd,dz->bsz", x, p["in_proj"].astype(compute_dtype))
        z, xBC, dt = _split_zxbcdt(cfg, zxbcdt)

        # causal depthwise conv over (x, B, C) features
        pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(
            pad[:, i : i + S, :] * p["conv_w"][i].astype(compute_dtype) for i in range(K)
        ) + p["conv_b"].astype(compute_dtype)
        conv = jax.nn.silu(conv.astype(jnp.float32)).astype(compute_dtype)

        x_in = conv[..., :di].reshape(B, S, H, P)
        x_in = constrain(x_in, "batch", None, "heads", None)
        Bm = conv[..., di : di + G * N].reshape(B, S, G, N)
        Cm = conv[..., di + G * N :].reshape(B, S, G, N)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    A = -jnp.exp(p["A_log"])  # (H,)

    y, final_state = ssd(
        x_in * dt[..., None].astype(compute_dtype),
        dt * A,
        Bm,
        Cm,
        cfg.ssm_chunk,
    )
    y = y + x_in * p["D"].astype(compute_dtype)[:, None]
    out = _gated_out(cfg, p, y.reshape(B, S, di), z, compute_dtype)
    out = constrain(out, "batch", None, None)

    cache = None
    if return_cache:
        cache = {"ssm": constrain(final_state.astype(jnp.float32),
                                  "batch", "heads", None, None)}
        if cfg.mamba_split_proj:
            cache["conv_x"] = pad_x.astype(compute_dtype)
            cache["conv_B"] = pad_B.astype(compute_dtype)
            cache["conv_C"] = pad_C.astype(compute_dtype)
        else:
            cache["conv"] = pad[:, -(K - 1) :, :].astype(compute_dtype)
    return out, cache


def mamba_decode(
    cfg: ModelConfig,
    p: Dict,
    x: jax.Array,  # (B, 1, d)
    cache: Dict,  # {"ssm": (B,H,P,N) f32, "conv": (B,K-1,conv_dim)}
    compute_dtype,
) -> Tuple[jax.Array, Dict]:
    B = x.shape[0]
    di, N, G, H, K = cfg.d_inner, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_heads, cfg.conv_kernel
    P = cfg.ssm_head_dim

    def conv_step(feat, state, w, b):
        win = jnp.concatenate([state, feat[:, None]], axis=1)  # (B, K, c)
        out = jnp.einsum("bkc,kc->bc", win, w.astype(compute_dtype)) + b.astype(
            compute_dtype
        )
        return jax.nn.silu(out.astype(jnp.float32)).astype(compute_dtype), win[:, 1:]

    new_conv = {}
    if cfg.mamba_split_proj:
        z = jnp.einsum("bsd,dz->bsz", x, p["w_z"].astype(compute_dtype))
        xs = jnp.einsum("bsd,dz->bsz", x, p["w_x"].astype(compute_dtype))[:, 0]
        Bs = jnp.einsum("bsd,dz->bsz", x, p["w_B"].astype(compute_dtype))[:, 0]
        Cs = jnp.einsum("bsd,dz->bsz", x, p["w_C"].astype(compute_dtype))[:, 0]
        dt = jnp.einsum("bsd,dz->bsz", x, p["w_dt"].astype(compute_dtype))
        xs, new_conv["conv_x"] = conv_step(xs, cache["conv_x"], p["conv_x_w"], p["conv_x_b"])
        Bs, new_conv["conv_B"] = conv_step(Bs, cache["conv_B"], p["conv_B_w"], p["conv_B_b"])
        Cs, new_conv["conv_C"] = conv_step(Cs, cache["conv_C"], p["conv_C_w"], p["conv_C_b"])
        x_in = xs.reshape(B, H, P)
        Bm = Bs.reshape(B, G, N)
        Cm = Cs.reshape(B, G, N)
    else:
        zxbcdt = jnp.einsum("bsd,dz->bsz", x, p["in_proj"].astype(compute_dtype))
        z, xBC, dt = _split_zxbcdt(cfg, zxbcdt)
        xBC = xBC[:, 0]  # (B, conv_dim)
        conv, new_conv["conv"] = conv_step(xBC, cache["conv"], p["conv_w"], p["conv_b"])
        x_in = conv[:, :di].reshape(B, H, P)
        Bm = conv[:, di : di + G * N].reshape(B, G, N)
        Cm = conv[:, di + G * N :].reshape(B, G, N)
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=1)  # (B,H,N)
    Ch = jnp.repeat(Cm, rep, axis=1)

    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)  # (B,H)

    state = cache["ssm"]  # (B,H,P,N) f32
    upd = jnp.einsum(
        "bh,bhn,bhp->bhpn", dt, Bh.astype(jnp.float32), x_in.astype(jnp.float32)
    )
    state = state * dA[..., None, None] + upd
    state = constrain(state, "batch", "heads", None, None)

    y = jnp.einsum("bhpn,bhn->bhp", state.astype(compute_dtype), Ch)
    y = y + x_in * p["D"].astype(compute_dtype)[:, None]
    out = _gated_out(cfg, p, y.reshape(B, 1, di), z, compute_dtype)
    return out, {"ssm": state, **new_conv}
