#!/usr/bin/env python3
"""Chip smoke test: serve full-width qwen1.5-0.5b cold restores on one TPU.

  python3 chip_smoke.py [--seed 0]

Drives the serving path once, in this one process, through the entry
points of ``repro.launch.serve``: catalog -> router -> node ->
SpiceRestorer -> device install -> generate, with random weights made from
``--seed``.  Phases, in order:

  device     JAX must report a TPU; there is no CPU fallback.
  publish    full-width params; the base goes into the node cache and two
             functions are published into a fresh directory: the base and
             a delta fine-tune of its top ~40% of layers.
  reference  first token of each function from ``lm.forward`` in float32
             on the same chip, on a prompt where the two differ, so the
             token served by the fused restore shows it served the delta.
  eager      a cold, a joined (concurrent) and a warm request of the base
             on an ``install="eager"`` node.
  fused      a cold ``spice`` restore of the delta on an ``install="fused"``
             node: private pages upload, the overlay kernel patches them
             against the HBM-resident base.
  fused-failure  the same restore with the device patch made to raise: the
             invocation must fail with that error.

Each restored device tree must equal the published params leaf by leaf,
and each served first token must equal the reference's.  Any failed phase
exits non-zero.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "qwen1.5-0.5b"
PROMPT_LEN = 16
CANDIDATES = 128  # prompts searched for one the fine-tune answers differently


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info():
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    check(info["platform"] == "tpu",
          f"no TPU found: JAX reports platform {info['platform']!r}")
    return info


class CompileCounter:
    """Counts compiles that asked the persistent cache, and its hits."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def reference_fn(cfg):
    """Jitted float32 ``lm.forward``: (first token, top-2 logit margin) per
    prompt row."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    @jax.jit
    def first_token(p, toks):
        logits, _, _ = lm.forward(cfg, p, {"tokens": toks},
                                  compute_dtype=jnp.float32, logits_mode="last")
        top2 = jax.lax.top_k(logits[:, -1], 2)[0]
        return jnp.argmax(logits[:, -1], -1), top2[:, 0] - top2[:, 1]

    def run(params, prompts):
        tok, margin = first_token(params, prompts)
        return np.asarray(tok, np.int32), np.asarray(margin)

    return run


def pick_prompt(cfg, published, base_fn, tuned_fn, seed):
    """One prompt on which the float32 reference gives the base and the
    fine-tune different first tokens, so a served token tells which weights
    were restored.  Of ``CANDIDATES`` prompts made from ``seed``, the one
    whose smaller top-2 margin is largest.  Returns the prompt and each
    function's (token, margin) on it."""
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (CANDIDATES, PROMPT_LEN),
                           dtype=np.int32)
    ref = reference_fn(cfg)
    refs = {f: ref(p, prompts) for f, p in published.items()}
    (base_tok, base_m), (tuned_tok, tuned_m) = refs[base_fn], refs[tuned_fn]
    differ = np.flatnonzero(base_tok != tuned_tok)
    print(f"reference: {differ.size} of {CANDIDATES} prompts give the base "
          f"and the fine-tune different first tokens", flush=True)
    check(differ.size > 0,
          "reference: no prompt tells the base from the fine-tune")
    row = differ[np.argmax(np.minimum(base_m, tuned_m)[differ])]
    return prompts[row:row + 1], {
        f: (tok[row:row + 1], m[row:row + 1]) for f, (tok, m) in refs.items()
    }


def check_tokens(label, result, ref):
    toks, margin = ref
    got = np.asarray(result.tokens)[:, 0]
    if not np.array_equal(got, toks):
        raise SmokeFailure(
            f"{label}: first tokens {got.tolist()} != reference "
            f"{toks.tolist()} (reference top-2 margins {margin.tolist()})"
        )


def check_tree(label, node, fname, cfg, params):
    """The WARM device tree equals the published state, leaf by leaf."""
    import jax

    from repro.serve.engine import layerwise_state

    inst = node.scheduler.instance(fname)
    check(inst is not None, f"{label}: no instance of {fname}")
    want = layerwise_state(cfg, params)
    chip = jax.devices()[0]
    with inst.pinned_warm_tree() as tree:
        check(jax.tree.structure(tree) == jax.tree.structure(want),
              f"{label}: restored tree structure differs")
        got = jax.tree.leaves_with_path(tree)
        for (path, arr), ref in zip(got, jax.tree.leaves(want)):
            name = jax.tree_util.keystr(path)
            check(isinstance(arr, jax.Array) and arr.devices() == {chip},
                  f"{label}: {name} is not on the chip ({type(arr).__name__})")
            check(np.array_equal(np.asarray(arr), ref),
                  f"{label}: {name} differs from the published params")
    print(f"{label}: {len(got)} leaves on device equal the published params",
          flush=True)


def _failed_fused_request(submit):
    """Submit with the overlay patch made to raise, as a kernel the chip
    refuses would; the invocation must fail with that error, not hang or
    fall back.  Returns the exception it raised."""
    from repro.kernels.overlay_patch import ops

    class Refused(RuntimeError):
        pass

    def refused(*_args):
        raise Refused("overlay patch refused (injected)")

    real, ops.overlay_patch_device = ops.overlay_patch_device, refused
    try:
        handle = submit()
        err = handle.exception(timeout=600)
    finally:
        ops.overlay_patch_device = real
    check(err is not None, "fused-failure: the invocation succeeded")
    cause = err
    while cause is not None and not isinstance(cause, Refused):
        cause = cause.__cause__ or cause.__context__
    check(cause is not None,
          f"fused-failure: the invocation failed with something else: {err!r}")
    return err


def run(cfg, seed: int) -> dict:
    """Every phase after the device check; returns the printed counts."""
    from repro.launch import serve
    from repro.launch.compile_cache import cache_entries, enable_compile_cache

    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    compiles = CompileCounter()
    with contextlib.ExitStack() as cleanup:
        counts = _phases(cfg, seed, serve, cleanup)
    counts.update(
        compile_cache_dir=str(cache_dir),
        compile_cache_entries_before=entries_before,
        compile_cache_entries_after=cache_entries(cache_dir),
        compiles=compiles.requests,
        compile_cache_hits=compiles.hits,
        fresh_compiles=compiles.requests - compiles.hits,
    )
    for k, v in counts.items():
        print(f"count: {k}={v}", flush=True)
    return counts


def _phases(cfg, seed, serve, cleanup) -> dict:
    from repro.serve.engine import Invocation

    t0 = time.perf_counter()

    def elapsed():
        return f"{time.perf_counter() - t0:.1f} s into the run"

    # ---- publish ------------------------------------------------------------
    params = serve.model_params(cfg, seed)
    nbytes = serve.image_bytes(params)
    print(f"publish: {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
          f"vocab={cfg.vocab_size} image_bytes={nbytes}", flush=True)
    workdir = cleanup.enter_context(tempfile.TemporaryDirectory(prefix="chip-smoke-"))
    eager = serve.serving_node("eager", nbytes, keep_warm=True, name="eager")
    cleanup.callback(eager.close)
    base_image = serve.install_base(eager, cfg, params)
    published = serve.publish_functions(eager, workdir, cfg, params)
    print(f"phase publish: ok ({sorted(published)} into a fresh directory, "
          f"{elapsed()})", flush=True)

    # ---- reference ------------------------------------------------------------
    prompt, refs = pick_prompt(cfg, published, serve.BASE_FN, serve.TUNED_FN,
                               seed)
    for f, (tok, margin) in refs.items():
        print(f"reference: {f} first token {tok.tolist()} "
              f"top-2 margin {margin.tolist()}", flush=True)

    # ---- eager: cold + joined (concurrent), then warm -----------------------
    def request(node, fname):
        return node.submit_invocation(Invocation(
            function=fname, prompt=prompt, max_new_tokens=2, cfg=cfg))

    h_cold = request(eager, serve.BASE_FN)
    h_join = request(eager, serve.BASE_FN)
    results = [h_cold.result(), h_join.result()]
    results.append(request(eager, serve.BASE_FN).result())
    kinds = ["warm" if not r.cold else "joined" if r.joined else "cold"
             for r in results]
    check(sorted(kinds) == ["cold", "joined", "warm"],
          f"eager: expected one cold, one joined, one warm request, got {kinds}")
    for k, r in zip(kinds, results):
        check_tokens(f"eager/{k}", r, refs[serve.BASE_FN])
    check_tree("eager", eager, serve.BASE_FN, cfg, params)
    cold_eager = results[kinds.index("cold")]
    eager_stats = dict(eager.scheduler.stats)
    eager_mem = dict(eager.memory.stats)
    eager_hw = eager.memory.high_water()["total"]
    eager.evict()
    eager.close()  # frees its device tree before the fused node restores
    print(f"phase eager: ok ({', '.join(kinds)}, {elapsed()})", flush=True)

    # ---- fused: cold spice restore of the delta -----------------------------
    fused = serve.serving_node("fused", nbytes, keep_warm=True, name="fused",
                               registry=eager.registry)
    cleanup.callback(fused.close)
    serve.install_base(fused, cfg, params, image=base_image)
    r_fused = request(fused, serve.TUNED_FN).result()
    check(r_fused.cold and not r_fused.joined and r_fused.mode == "spice",
          "fused: the delta request was not a cold spice restore")
    check_tokens("fused/cold", r_fused, refs[serve.TUNED_FN])
    check_tree("fused", fused, serve.TUNED_FN, cfg, published[serve.TUNED_FN])
    up = fused.scheduler.upload_stream.snapshot_stats()
    st = r_fused.stats
    print(f"fused: tensors fused={st['fused_tensors']} "
          f"full_upload={st['full_upload_tensors']} "
          f"fused_patches={up['fused_patches']} uploads={up['uploads']} "
          f"failures={up['failures']}", flush=True)
    check(up["failures"] == 0, f"fused: {up['failures']} upload failures")
    check(up["fused_patches"] > 0 and st["fused_tensors"] > 0,
          "fused: every tensor took the full-upload path")
    fused_stats = dict(fused.scheduler.stats)
    fused_mem = dict(fused.memory.stats)
    fused_hw = fused.memory.high_water()["total"]
    print(f"phase fused: ok ({elapsed()})", flush=True)

    # ---- fused-failure: a device patch that raises fails the invocation -----
    fused.evict()
    err = _failed_fused_request(lambda: request(fused, serve.TUNED_FN))
    failures = fused.scheduler.upload_stream.snapshot_stats()["failures"]
    print(f"fused-failure: invocation raised {type(err).__name__}: "
          f"{str(err).splitlines()[0]}; upload failures={failures}", flush=True)
    check(failures > 0, "fused-failure: the upload stream counted no failure")
    check(fused.scheduler.instance(serve.TUNED_FN).state.value != "warm",
          "fused-failure: the failed restore left a WARM instance")
    print(f"phase fused-failure: ok ({elapsed()})", flush=True)

    for label, mem in (("eager", eager_mem), ("fused", fused_mem)):
        check(mem["pressure_failures"] == 0 and mem["pressure_waits"] == 0,
              f"{label}: the ledger ran short: {mem}")

    def total(key):
        return eager_stats[key] + fused_stats[key]

    return {
        "requests": total("invocations"),
        "cold": total("cold_starts"),
        "joined": total("joined_restores"),
        "warm": total("warm_hits"),
        "eager_bytes_read": cold_eager.stats["bytes_read"],
        "eager_base_bytes": cold_eager.stats["base_bytes"],
        "fused_bytes_read": st["bytes_read"],
        "fused_uploaded_bytes": st["uploaded_bytes"],
        "fused_patched_bytes": st["patched_on_device_bytes"],
        "ledger_budget": fused.scheduler.memory_budget,
        "eager_ledger_high_water": eager_hw,
        "fused_ledger_high_water": fused_hw,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        info = device_info()
        from repro.configs import get_config

        run(get_config(ARCH), args.seed)
    except Exception:  # noqa: BLE001 — any failed phase fails the smoke
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
