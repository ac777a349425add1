"""The mamba2-780m configuration end to end on the CPU at a small size: a
whole run of each traffic mix reads ``correct: true``, and each fault of
the timed path (the old gate order, an altered token, a restore that drops
its private pages) and the int8 control read ``correct: false``.  Also the
reference's sizes and FLOPs, and the upload ring's issue reader."""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.references import mamba2_lm
from chipbench.tests.small import small_mix
from chipbench.tests.test_chipbench_spans import COLD, _ev, _req, _run as _trace_run

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIG = harness.HERE / "configs" / "mamba2-780m.json"
MIXES = sorted(p.stem for p in (harness.HERE / "traffic").glob("*.json"))
CELLS = [f"mamba2-780m.{m}" for m in MIXES]
TEST_BENCH = dict(BENCH, workloads=[
    {"name": f"mamba2-780m.{m}", "config": "mamba2-780m", "traffic": m, "chips": 1,
     "why": m} for m in MIXES])
VOCAB = 4096


def small_config(layers=2, init_std=0.02):
    """The benchmark's file with its sizes cut: d_model 64, 4 heads of 16,
    d_state 16, chunk 8 (the prompts' 16 tokens are two chunks)."""
    c = json.loads(CONFIG.read_text())
    c.update(d_model=64, n_layer=layers, vocab_size=VOCAB, initializer_range=init_std)
    c["ssm_cfg"] = dict(c["ssm_cfg"], d_state=16, headdim=16, chunk_size=8)
    c["program"] = {"arch": "mamba2-780m", "overrides": {
        "name": "mamba2-bench-small", "d_model": 64, "n_layers": layers,
        "pattern_reps": layers, "vocab_size": VOCAB, "ssm_state": 16,
        "ssm_head_dim": 16, "ssm_chunk": 8}}
    return c


def _run(cell, seed=2**31 + 11, **kw):
    w = harness.workload(TEST_BENCH, cell)
    mix = small_mix(harness.load_json(harness.HERE / "traffic" / f"{w['traffic']}.json"))
    return harness.run_cell(cell, seed, 1.0, False, t_process=time.perf_counter(),
                            require_tpu=False, bench=TEST_BENCH, config=small_config(),
                            mix=mix, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e, _ = harness.cell_metrics(TEST_BENCH, cell)
    assert set(out["metrics"]) == {m["name"] for m in e2e}


@pytest.mark.parametrize("cell", CELLS)
def test_old_gate_order_fails(cell, monkeypatch):
    # the order the program computed before: RMSNorm(y) * w * silu(z)
    from repro.models import layers, mamba2
    from repro.serve import instance

    def norm_then_gate(cfg, p, y, z, compute_dtype):
        y = layers.rmsnorm(y, p["norm_w"], cfg.norm_eps) * jax.nn.silu(
            z.astype(jnp.float32)).astype(compute_dtype)
        return jnp.einsum("bsi,id->bsd", y, p["out_proj"].astype(compute_dtype))

    monkeypatch.setattr(mamba2, "_gated_out", norm_then_gate)
    monkeypatch.setattr(instance, "_COMPILE_CACHE", {})  # trace the layers anew
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["stream_gap"]["value"] > out["checks"]["stream_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_fails(cell, monkeypatch):
    from repro.serve import instance

    real = instance._head_fn

    def head_fn(cfg):
        fn = real(cfg)
        return lambda *a: (fn(*a) + 1) % cfg.vocab_size

    monkeypatch.setattr(instance, "_head_fn", head_fn)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > out["checks"]["token_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_restore_that_drops_private_pages_fails(cell, monkeypatch):
    from repro.kernels.overlay_patch import ops

    real = ops.overlay_patch_device

    def base_only(base, priv, kinds, src):
        return real(base, priv, np.where(np.asarray(kinds) == 2, 1, kinds), src)

    monkeypatch.setattr(ops, "overlay_patch_device", base_only)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["restore_leaves_differ"]["value"] > 0


def test_int8_control_fails():
    # Judged by the run's own checks.  Long prompts, 8 layers and logits
    # about as wide as the full model's (initializer 0.02 x sqrt(1536 / 64),
    # about 0.1), so that int8 rounding moves some positions' first tokens:
    # seeds 1 to 5 read 0.154 to 0.211 here.
    cell = CELLS[-1]
    w = harness.workload(TEST_BENCH, cell)
    mix = dict(small_mix(harness.load_json(harness.HERE / "traffic" / f"{w['traffic']}.json")),
               prompt_len=512)
    out = harness.run_cell(cell, 2, 1.0, False, t_process=time.perf_counter(),
                           require_tpu=False, bench=TEST_BENCH,
                           config=small_config(layers=8, init_std=0.1), mix=mix,
                           control=True)
    ctl, prog = out["control"], out["checks"]
    assert out["correct"], prog
    assert not ctl["int8"]["correct"]
    assert ctl["int8"]["stream_gap"] > prog["stream_gap"]["limit"]
    assert not ctl["altered"]["correct"]


def test_dims_agree_with_the_published_keys():
    config = json.loads(CONFIG.read_text())
    dm = mamba2_lm.dims(config)
    assert (dm.d, dm.layers, dm.vocab, dm.state, dm.head_dim, dm.inner, dm.groups,
            dm.conv, dm.heads, dm.eps) == (1536, 48, 50288, 128, 64, 3072, 1, 4, 48, 1e-5)
    # and the program's preset runs exactly these sizes
    cfg = harness.program_config(config, mamba2_lm, dm)
    assert (cfg.ssm_chunk, cfg.tie_embeddings) == (config["ssm_cfg"]["chunk_size"], True)


@pytest.mark.parametrize("key,value", [("norm_before_gate", True), ("D_has_hdim", True),
                                       ("layer", "Mamba1")])
def test_dims_refuse_another_mixer(key, value):
    config = json.loads(CONFIG.read_text())
    config["ssm_cfg"][key] = value
    with pytest.raises(ValueError):
        mamba2_lm.dims(config)


def test_flops_hand_count():
    # mamba2-780m at a 1024-token prompt, counted by hand: per layer
    # in_proj 1536 x 6448 and out_proj 3072 x 1536 multiply-adds per token,
    # the recurrence 5 x 48 x 64 x 128 per token; the tied head once
    dm = mamba2_lm.dims(json.loads(CONFIG.read_text()))
    S = 1024
    proj = 48 * 2 * (1536 * 6448 + 3072 * 1536) * S
    scan = 48 * 5 * 48 * 64 * 128 * S
    head = 2 * 50288 * 1536
    assert mamba2_lm.flops(dm, S) == proj + scan + head == 1_534_263_115_776


def test_issue_time_is_the_union_of_the_issuer_spans_in_each_request():
    host = [_req(0, 100), _req(200, 300),
            # request 1: 10-40 of two overlapping issues, the put inside one
            # and 95-100 of one straddling its end: 35 ms; request 2:
            # 210-230: 20 ms
            _ev("spice.upload.issue", 10, 30, "issuer"), _ev("spice.upload.issue", 20, 40, "issuer"),
            _ev("spice.upload.put", 12, 18, "issuer"),
            _ev("spice.upload.issue", 95, 110, "issuer"),
            _ev("spice.upload.issue", 210, 230, "issuer"),
            # the lander's waits are not issue time
            _ev("spice.upload.land", 40, 90, "lander")]
    assert harness.reader("issue_ms.cold")(_trace_run(host, COLD)) == pytest.approx(27.5)


def test_no_issue_span_reads_none():
    # a program that records no issue span reads nothing
    host = [_req(0, 100), _ev("spice.upload.put", 10, 20, "issuer")]
    run = _trace_run(host, COLD[:1])
    assert harness.reader("issue_ms.cold")(run) is None
    run.trace = None
    assert harness.reader("issue_ms.cold")(run) is None


def test_issue_metric_reads_both_cold_cells():
    m = next(m for m in BENCH["per_layer"] if m["name"] == "issue_ms.cold")
    assert m["source"] == "program_span" and m["layer"] == "upload ring"
    assert m["workloads"] == ["qwen1.5-0.5b.cold", "mamba2-780m.cold"]
    assert m["moves"] == "cold_ttft_p50_ms"
