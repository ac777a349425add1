"""Subprocess worker: numerical equivalence of the sharded paths vs the
single-device oracle, on 8 fake host devices. Invoked by test_distributed.py
(device count must be fixed before jax initializes)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.specs import make_rules
from repro.configs.base import InputShape
from repro.models import lm
from repro.models.moe import moe_ffn
from repro.models.layers import embed
from repro.sharding.partition import axis_rules
from repro.train.steps import TrainStepConfig, init_train_state, make_train_step


def mesh_2d():
    from repro.launch.mesh import make_mesh

    return make_mesh((2, 4), ("data", "model"))


def check_moe_and_embed():
    # capacity big enough that no token drops: per-shard capacity enforcement
    # (sharded EP) must then agree exactly with the global-capacity oracle
    cfg = dataclasses.replace(get_config("olmoe-1b-7b").reduced(), capacity_factor=8.0)
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)

    ref_logits, _, ref_aux = jax.jit(
        lambda p, t: lm.forward(cfg, p, {"tokens": t}, mode="train",
                                compute_dtype=jnp.float32)
    )(params, toks)

    mesh = mesh_2d()
    rules = make_rules(cfg, InputShape("t", "train", 16, 4), False)
    with mesh, axis_rules(mesh, rules):
        sh_logits, _, sh_aux = jax.jit(
            lambda p, t: lm.forward(cfg, p, {"tokens": t}, mode="train",
                                    compute_dtype=jnp.float32)
        )(params, toks)
    np.testing.assert_allclose(
        np.asarray(ref_logits), np.asarray(sh_logits), rtol=2e-4, atol=2e-4
    )
    # sharded aux is the standard per-device LBL (mean of per-shard products
    # != product of global means): approximate agreement only
    np.testing.assert_allclose(float(ref_aux), float(sh_aux), rtol=0.25)
    print("moe+embed sharded == local: OK")


def check_moe_decode_path():
    """replicated-token EP mode (S=1) against the local path."""
    cfg = dataclasses.replace(
        get_config("phi3.5-moe-42b-a6.6b").reduced(), capacity_factor=8.0
    )
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    caches = lm.init_cache(cfg, 4, 32, kv_dtype=jnp.float32, compute_dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 1), 0, cfg.vocab_size)

    ref, _, _ = jax.jit(
        lambda p, t, c: lm.decode_step(cfg, p, {"tokens": t}, c, jnp.int32(3),
                                       compute_dtype=jnp.float32)
    )(params, toks, caches)
    mesh = mesh_2d()
    rules = make_rules(cfg, InputShape("d", "decode", 32, 4), False)
    with mesh, axis_rules(mesh, rules):
        got, _, _ = jax.jit(
            lambda p, t, c: lm.decode_step(cfg, p, {"tokens": t}, c, jnp.int32(3),
                                           compute_dtype=jnp.float32)
        )(params, toks, caches)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=2e-4, atol=2e-4)
    print("moe decode (replicated EP) sharded == local: OK")


def check_train_step():
    cfg = get_config("qwen1.5-0.5b").reduced()
    tcfg = TrainStepConfig(remat="dots", compute_dtype="float32",
                           num_microbatches=2, kv_repeat=2)
    params, opt = init_train_state(cfg, jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab_size),
    }
    step = make_train_step(cfg, tcfg)
    p_ref, _, m_ref = jax.jit(step)(params, opt, batch)

    mesh = mesh_2d()
    rules = make_rules(cfg, InputShape("t", "train", 32, 4), False)
    with mesh, axis_rules(mesh, rules):
        p_sh, _, m_sh = jax.jit(step)(params, opt, batch)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_sh["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4)
    print("train_step sharded == local: OK")


def check_elastic_reshard():
    from repro.ft.elastic import make_mesh_from_plan, plan_mesh, reshard_state
    from repro.models.lm import param_specs

    cfg = get_config("qwen1.5-0.5b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    state_np = jax.tree.map(np.asarray, params)
    plan = plan_mesh(8, model_parallel=4)
    assert plan.shape == (2, 4)
    mesh = make_mesh_from_plan(plan)
    rules = make_rules(cfg, InputShape("t", "train", 32, 4), False)
    placed = reshard_state(state_np, param_specs(cfg), mesh, rules)
    for a, b in zip(jax.tree.leaves(placed), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # scale-down: 6 devices -> (1, 4) mesh w/ 2 idle, state still placeable
    plan2 = plan_mesh(6, model_parallel=4)
    mesh2 = make_mesh_from_plan(plan2)
    placed2 = reshard_state(state_np, param_specs(cfg), mesh2, rules)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(placed2)[0]), np.asarray(jax.tree.leaves(params)[0])
    )
    print("elastic reshard: OK")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    fns = {
        "moe": check_moe_and_embed,
        "moe_decode": check_moe_decode_path,
        "train": check_train_step,
        "elastic": check_elastic_reshard,
    }
    if which == "all":
        for f in fns.values():
            f()
    else:
        fns[which]()
    print("DISTRIBUTED_CHECKS_PASSED")
