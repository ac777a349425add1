"""Top-k MoE with capacity-based dispatch.

Two execution paths:

* **local** (no sharding rules active — smoke tests, benchmarks): dense
  scatter/gather dispatch on one device.
* **shard_map EP** (under ``axis_rules``): expert parallelism over the
  ``model`` mesh axis with *explicit* collectives, because GSPMD's handling
  of data-dependent scatter/gather across an expert-sharded buffer degrades
  to full rematerialization (observed: 288 GB/device temp on olmoe).
  - ``a2a`` mode (train/prefill: seq divisible by the model axis): tokens are
    sharded over (dp x model); each device dispatches into an (E, C_dev, d)
    buffer and a pair of all-to-alls moves tokens to/from expert owners —
    the GShard pattern.
  - ``replicated`` mode (decode: one token per sequence): every model rank
    routes the dp-local tokens, computes only its own E/m experts, and the
    outputs are psum'd over the model axis. Right trade-off for tiny T.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.sharding.partition import ParamSpec, current_rules, logical_to_spec

def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


W_LOGICAL = {
    "w_gate": ("expert", "fsdp", "model"),
    "w_up": ("expert", "fsdp", "model"),
    "w_down": ("expert", "model", "fsdp"),
}


def moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamSpec((d, E), (None, None), init="fanin", dtype=jnp.float32),
        "w_gate": ParamSpec((E, d, f), W_LOGICAL["w_gate"], init="fanin"),
        "w_up": ParamSpec((E, d, f), W_LOGICAL["w_up"], init="fanin"),
        "w_down": ParamSpec((E, f, d), W_LOGICAL["w_down"], init="fanin"),
    }


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(4, min(c, n_tokens * cfg.top_k))


def _route(cfg, router_w, xf):
    """xf: (T, d) -> gates (T,k), idx (T,k), probs (T,E) [f32]."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, idx, probs


def _positions(idx, E: int, C: int):
    """Slot positions within each expert for (T,k) routed pairs."""
    T, k = idx.shape
    oh = jax.nn.one_hot(idx, E, dtype=jnp.int32).reshape(T * k, E)
    pos = jnp.cumsum(oh, axis=0) - oh
    flat_pos = jnp.sum(pos * oh, axis=-1)
    flat_e = idx.reshape(T * k)
    keep = flat_pos < C
    return flat_e, jnp.minimum(flat_pos, C - 1), keep


def _aux_loss(cfg, probs, idx):
    T = probs.shape[0]
    oh = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)  # (T,k,E)
    f_e = jnp.mean(oh.sum(axis=1), axis=0)
    P_e = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(f_e * P_e) / cfg.top_k


def _expert_mlp(h_in, wg, wu, wd):
    h = jnp.einsum("ecd,edf->ecf", h_in, wg)
    u = jnp.einsum("ecd,edf->ecf", h_in, wu)
    h = jax.nn.silu(h.astype(jnp.float32)).astype(h_in.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _moe_local(cfg: ModelConfig, p: Dict, x, compute_dtype):
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    C = capacity(cfg, T)
    xf = x.reshape(T, d)
    gates, idx, probs = _route(cfg, p["router"], xf)
    flat_e, flat_pos, keep = _positions(idx, E, C)

    xr = jnp.broadcast_to(xf[:, None, :], (T, k, d)).reshape(T * k, d)
    buf = jnp.zeros((E, C, d), compute_dtype)
    buf = buf.at[flat_e, flat_pos].add(
        jnp.where(keep[:, None], xr, 0).astype(compute_dtype), mode="drop"
    )
    out = _expert_mlp(
        buf,
        p["w_gate"].astype(compute_dtype),
        p["w_up"].astype(compute_dtype),
        p["w_down"].astype(compute_dtype),
    )
    vals = out[flat_e, flat_pos]
    w = jnp.where(keep, gates.reshape(T * k), 0.0).astype(compute_dtype)
    y = (vals * w[:, None]).reshape(T, k, d).sum(axis=1)
    return y.reshape(B, S, d), _aux_loss(cfg, probs, idx)


def _gather_fsdp(w, spec: P, compute_dtype):
    """Inside shard_map: all-gather any FSDP-sharded weight dims, cast."""
    for axis_pos, ax in enumerate(spec):
        if ax is None or axis_pos == 0:  # dim 0 is the expert (EP) dim: keep
            continue
        names = (ax,) if isinstance(ax, str) else tuple(ax)
        for name in names:
            w = jax.lax.all_gather(w, name, axis=axis_pos, tiled=True)
    return w.astype(compute_dtype)


def moe_ffn(cfg: ModelConfig, p: Dict, x, compute_dtype) -> Tuple[jax.Array, jax.Array]:
    rules = current_rules()
    if rules is None:
        return _moe_local(cfg, p, x, compute_dtype)

    mesh = rules.mesh
    m_ax = "model"
    m = mesh.shape.get(m_ax, 1)
    E = cfg.n_experts
    B, S, d = x.shape
    dp_axes = rules.mapping.get("batch") or ()
    dp_axes = (dp_axes,) if isinstance(dp_axes, str) else tuple(dp_axes)
    dp = int(math.prod(mesh.shape[a] for a in dp_axes)) if dp_axes else 1

    batch_shardable = B % dp == 0 and dp > 1
    bspec = dp_axes if batch_shardable else None
    a2a = (E % m == 0) and (S % m == 0) and S > 1 and m > 1

    w_specs = {
        k: logical_to_spec(W_LOGICAL[k], p[k].shape, rules) for k in W_LOGICAL
    }
    all_axes = tuple(mesh.axis_names)

    if a2a:
        fn = partial(_moe_a2a_local, cfg, compute_dtype, m_ax, m, all_axes, w_specs)
        in_specs = (
            P(bspec, m_ax, None),
            P(None, None),
            w_specs["w_gate"],
            w_specs["w_up"],
            w_specs["w_down"],
        )
        out_specs = (P(bspec, m_ax, None), P())
    else:
        fn = partial(_moe_repl_local, cfg, compute_dtype, m_ax, m, all_axes, w_specs)
        in_specs = (
            P(bspec, None, None),
            P(None, None),
            w_specs["w_gate"],
            w_specs["w_up"],
            w_specs["w_down"],
        )
        out_specs = (P(bspec, None, None), P())

    y, aux = shard_map(fn, mesh, in_specs, out_specs)(
        x, p["router"], p["w_gate"], p["w_up"], p["w_down"]
    )
    return y, aux


def _moe_a2a_local(cfg, compute_dtype, m_ax, m, all_axes, w_specs,
                   xl, router, wg, wu, wd):
    """Per-device body, tokens sharded (dp x model): dispatch -> a2a ->
    expert mlp -> a2a back -> combine."""
    E, k = cfg.n_experts, cfg.top_k
    E_loc = E // m
    Bl, Sl, d = xl.shape
    T = Bl * Sl
    C = capacity(cfg, T)
    xf = xl.reshape(T, d)

    gates, idx, probs = _route(cfg, router, xf)
    flat_e, flat_pos, keep = _positions(idx, E, C)

    xr = jnp.broadcast_to(xf[:, None, :], (T, k, d)).reshape(T * k, d)
    buf = jnp.zeros((E, C, d), compute_dtype)
    buf = buf.at[flat_e, flat_pos].add(
        jnp.where(keep[:, None], xr, 0).astype(compute_dtype), mode="drop"
    )

    send = buf.reshape(m, E_loc, C, d)
    recv = jax.lax.all_to_all(send, m_ax, split_axis=0, concat_axis=0, tiled=False)
    x_e = recv.transpose(1, 0, 2, 3).reshape(E_loc, m * C, d)

    wg = _gather_fsdp(wg, w_specs["w_gate"], compute_dtype)
    wu = _gather_fsdp(wu, w_specs["w_up"], compute_dtype)
    wd = _gather_fsdp(wd, w_specs["w_down"], compute_dtype)
    out_e = _expert_mlp(x_e, wg, wu, wd)

    back = out_e.reshape(E_loc, m, C, d).transpose(1, 0, 2, 3)
    got = jax.lax.all_to_all(back, m_ax, split_axis=0, concat_axis=0, tiled=False)
    out = got.reshape(E, C, d)

    vals = out[flat_e, flat_pos]
    w = jnp.where(keep, gates.reshape(T * k), 0.0).astype(compute_dtype)
    y = (vals * w[:, None]).reshape(T, k, d).sum(axis=1).reshape(Bl, Sl, d)

    aux = jax.lax.pmean(_aux_loss(cfg, probs, idx), all_axes)
    return y, aux


def _moe_repl_local(cfg, compute_dtype, m_ax, m, all_axes, w_specs,
                    xl, router, wg, wu, wd):
    """Per-device body, tokens replicated over the model axis: each rank
    computes its E/m experts, outputs psum'd."""
    E, k = cfg.n_experts, cfg.top_k
    divisible = E % m == 0
    E_loc = E // m if divisible else E
    Bl, Sl, d = xl.shape
    T = Bl * Sl
    C = capacity(cfg, T)
    xf = xl.reshape(T, d)

    gates, idx, probs = _route(cfg, router, xf)
    flat_e, flat_pos, keep = _positions(idx, E, C)

    rank = jax.lax.axis_index(m_ax) if m > 1 else 0
    if divisible:
        e_start = rank * E_loc
        mine = keep & (flat_e >= e_start) & (flat_e < e_start + E_loc)
    else:  # experts unshardable: rank 0 computes everything (rare fallback)
        e_start = 0
        mine = keep & (rank == 0) if m > 1 else keep
    e_rel = jnp.clip(flat_e - e_start, 0, E_loc - 1)

    xr = jnp.broadcast_to(xf[:, None, :], (T, k, d)).reshape(T * k, d)
    buf = jnp.zeros((E_loc, C, d), compute_dtype)
    buf = buf.at[e_rel, flat_pos].add(
        jnp.where(mine[:, None], xr, 0).astype(compute_dtype), mode="drop"
    )

    wg = _gather_fsdp(wg, w_specs["w_gate"], compute_dtype)
    wu = _gather_fsdp(wu, w_specs["w_up"], compute_dtype)
    wd = _gather_fsdp(wd, w_specs["w_down"], compute_dtype)
    out = _expert_mlp(buf, wg, wu, wd)

    vals = out[e_rel, flat_pos]
    w = jnp.where(mine, gates.reshape(T * k), 0.0).astype(compute_dtype)
    y = (vals * w[:, None]).reshape(T, k, d).sum(axis=1)
    if m > 1:
        y = jax.lax.psum(y, m_ax)
    y = y.reshape(Bl, Sl, d)

    aux = jax.lax.pmean(_aux_loss(cfg, probs, idx), all_axes)
    return y, aux
