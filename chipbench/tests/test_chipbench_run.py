"""A whole run of each cell on the CPU at a small size, with the chip check
skipped, and the same run with the timed path broken underneath: each
fault must turn ``correct`` false."""
import json
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.small import small_config, small_mix

BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
# every mix file, as a cell of the benchmark's configuration
MIXES = sorted(p.stem for p in (harness.HERE / "traffic").glob("*.json"))
CONFIG = BENCH["configs"][0]["name"]
CELLS = [f"{CONFIG}.{m}" for m in MIXES]
TEST_BENCH = dict(BENCH, workloads=[
    {"name": f"{CONFIG}.{m}", "config": CONFIG, "traffic": m, "chips": 1, "why": m}
    for m in MIXES])


def _run(cell, seed=2**31 + 7):
    w = harness.workload(TEST_BENCH, cell)
    mix = small_mix(harness.load_json(harness.HERE / "traffic" / f"{w['traffic']}.json"))
    return harness.run_cell(cell, seed, 1.0, False, t_process=time.perf_counter(),
                            require_tpu=False, bench=TEST_BENCH, config=small_config(),
                            mix=mix)


@pytest.mark.parametrize("cell", CELLS)
def test_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e, _ = harness.cell_metrics(TEST_BENCH, cell)
    assert set(out["metrics"]) == {m["name"] for m in e2e}
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


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


def test_controls_read_above_the_program():
    # Judged by the run's own checks.  Long prompts, 8 layers and logits
    # about as wide as the full model's (initializer 0.02 x sqrt(1024 / 64)),
    # so that int8 rounding flips some positions' first tokens; at this
    # small size its gap swings about the limit from seed to seed, and
    # seed 5 reads 0.111 against 0.09.
    w = harness.workload(TEST_BENCH, CELLS[-1])
    mix = dict(small_mix(harness.load_json(harness.HERE / "traffic" / f"{w['traffic']}.json")),
               prompt_len=512)
    config = small_config()
    config.update(initializer_range=0.08, num_hidden_layers=8)
    config["program"]["overrides"].update(n_layers=8, pattern_reps=8)
    out = harness.run_cell(CELLS[-1], 5, 1.0, False, t_process=time.perf_counter(),
                           require_tpu=False, bench=TEST_BENCH, config=config,
                           mix=mix, control=True)
    ctl, prog = out["control"], out["checks"]
    assert out["correct"] and set(ctl) == set(harness.CONTROLS)
    assert not ctl["altered"]["correct"]
    assert ctl["altered"]["token_gap"] > prog["token_gap"]["limit"]
    assert not ctl["int8"]["correct"]
    assert ctl["int8"]["stream_gap"] > prog["stream_gap"]["limit"]


def test_sample_of_checked_pairs_is_seeded():
    done = [harness.Request(f, p, 0.01, token=f + p) for f in range(2) for p in range(16)]
    a = harness.sample_served(done, {}, 7)
    assert a == harness.sample_served(done, {}, 7)
    assert len(a["tokens"]) == harness.CHECKED
    assert a["tokens"] != harness.sample_served(done, {}, 8)["tokens"]
