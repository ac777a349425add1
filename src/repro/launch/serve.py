"""End-to-end serving entry point: publish a base model and a delta fine-tune of
it, then serve requests of the fine-tune with cold restores (the Spice
serving loop).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --requests 8 [--keep-warm | --prewarm] [--full-config]

Uses the reduced config by default (CPU); ``--full-config`` serves the
published widths, which needs an accelerator.  The node's memory ledger is
sized from the bytes of the published image, so the full widths fit.

What is served is a delta fine-tune of a base pinned in the node's image
cache, as on a platform that hosts many fine-tunes of a few bases.  A cold
restore therefore serves mostly BASE pages from the node cache and reads
only the fine-tune's private pages from storage.

Warmth modes:
  (none)       every request is a cold start (no keep-alive)
  --keep-warm  reactive: static 300 s keep-alive TTL (the pre-policy knob)
  --prewarm    predictive: adaptive per-function TTLs from the arrival
               histogram (PrewarmPolicy) + speculative restores ahead of
               the predicted next arrival (PrewarmEngine)

The helpers below are the serving path's entry points; ``chip_smoke.py``
drives the same ones on the chip.
"""
from __future__ import annotations

import argparse
import tempfile
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import BaseImage, FunctionRegistry
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve.engine import (
    ArrivalTracker,
    FixedTTLPolicy,
    Invocation,
    PrewarmEngine,
    PrewarmPolicy,
    ServerlessNode,
    layerwise_state,
)

BASE_IMAGE = "base-image"  # the operator-installed base in the node cache
BASE_FN = "base"           # function published from the base weights
TUNED_FN = "tuned"         # delta fine-tune published against the base
# A node holds at most this many images' worth of bytes at once: the
# cached base, one publish's scratch copy, a restored instance and its
# staging buffers (the device base pages of a fused node fit in the slack).
LEDGER_IMAGES = 4
FINETUNE_SCALE = 0.02  # relative weight change of the published fine-tune


def model_params(cfg: ModelConfig, seed: int):
    """Random float32 weights at ``cfg``'s widths, made from ``seed``."""
    return lm.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)


def finetune(cfg: ModelConfig, params, scale: float):
    """A delta fine-tune of ``params``: the top ~40% of the layer stack, an
    untied output head and ~0.2% of the token embedding rows scale by
    ``1 + scale`` and the final norm shifts by ``scale``; everything else
    stays byte-identical to the base, so its pages dedup against the base
    image (and the embedding restores as mostly-BASE with a few private
    pages)."""
    cut = int(cfg.pattern_reps * 0.6)
    rows = max(1, cfg.vocab_size // 512)

    def bump(a):
        if a.ndim >= 1 and a.shape[0] == cfg.pattern_reps:
            return a.at[cut:].multiply(1.0 + scale)
        return a

    tuned = dict(params)
    tuned["final_norm"] = params["final_norm"] + scale
    embed = dict(params["embed"])
    embed["tok"] = embed["tok"].at[:rows].multiply(1.0 + scale)
    if "unembed" in embed:
        embed["unembed"] = embed["unembed"] * (1.0 + scale)
    tuned["embed"] = embed
    tuned["pattern"] = [jax.tree.map(bump, p) for p in params["pattern"]]
    return tuned


def image_bytes(params) -> int:
    """Bytes of the state a function's image publishes."""
    return int(sum(a.nbytes for a in jax.tree.leaves(params)))


def serving_node(install: str, nbytes: int, *, keep_warm: bool = False,
                 registry: Optional[FunctionRegistry] = None,
                 **node_kwargs) -> ServerlessNode:
    """A node whose ledger budget is sized from the published image bytes
    (``LEDGER_IMAGES`` images), so a full-width function fits where the
    default staging-pool budget would refuse it."""
    if keep_warm:
        node_kwargs["keepalive"] = FixedTTLPolicy(300.0)
    return ServerlessNode(
        registry=registry, install=install,
        memory_budget_bytes=LEDGER_IMAGES * nbytes, **node_kwargs,
    )


def install_base(node: ServerlessNode, cfg: ModelConfig, params,
                 image: Optional[BaseImage] = None) -> BaseImage:
    """Put the base weights in ``node``'s image cache (pinned: no JIF backs
    an operator-installed base).  Pass ``image`` to share one host copy
    between nodes."""
    if image is None:
        image = BaseImage.from_state(BASE_IMAGE, layerwise_state(cfg, params))
    node.node_cache.put(image, evictable=False)
    return image


def publish_functions(node: ServerlessNode, dirpath: str, cfg: ModelConfig,
                      params) -> Dict:
    """Publish the base and its delta fine-tune against the cached base
    image; returns ``{function: params}`` for what each one serves."""
    published = {BASE_FN: params,
                 TUNED_FN: finetune(cfg, params, FINETUNE_SCALE)}
    for fname, p in published.items():
        node.publish(fname, cfg, p, dirpath, base_name=BASE_IMAGE)
    return published


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published widths (needs an accelerator)")
    ap.add_argument("--interval", type=float, default=0.0,
                    help="seconds between requests (gives --prewarm a "
                         "periodic arrival pattern to learn)")
    warmth = ap.add_mutually_exclusive_group()
    warmth.add_argument("--keep-warm", action="store_true",
                        help="reactive keep-alive: static 300 s TTL")
    warmth.add_argument("--prewarm", action="store_true",
                        help="predictive: adaptive TTLs + speculative "
                             "restores from the arrival histogram")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.reduced()
    params = model_params(cfg, 0)
    nbytes = image_bytes(params)
    kw = {}
    if args.prewarm:
        tracker = ArrivalTracker()
        kw = dict(
            keepalive=PrewarmPolicy(
                tracker, default_ttl_s=0.0, max_ttl_s=300.0,
                min_observations=2,
            ),
            prewarm=PrewarmEngine(
                tracker, horizon_s=max(0.3, args.interval),
                interval_s=0.05, min_observations=2,
            ),
            reap_interval_s=0.25,
        )
    # without a warmth mode the spec TTL is 0: every request restores
    node = serving_node("eager", nbytes, keep_warm=args.keep_warm, **kw)
    with tempfile.TemporaryDirectory() as d:
        install_base(node, cfg, params)
        publish_functions(node, d, cfg, params)
        prompt = np.tile(np.arange(1, args.prompt_len + 1, dtype=np.int32),
                         (args.batch, 1))
        # compile-cache warmup
        node.submit_invocation(Invocation(
            function=TUNED_FN, prompt=prompt, max_new_tokens=2, cfg=cfg,
        )).result()
        node.evict()

        print(f"{'req':>4} {'path':>6} {'ttft_ms':>9} {'total_ms':>9}")
        for i in range(args.requests):
            if not (args.keep_warm or args.prewarm):
                node.evict()
            r = node.submit_invocation(Invocation(
                function=TUNED_FN, prompt=prompt, max_new_tokens=args.max_new,
                cfg=cfg,
            )).result()
            path = "warm" if not r.cold else ("join" if r.joined else "cold")
            print(f"{i:>4} {path:>6} "
                  f"{r.ttft_s*1e3:9.2f} {r.total_s*1e3:9.2f}")
            if args.interval:
                time.sleep(args.interval)
        print("pool:", node.pool.stats)
        if args.prewarm:
            eng = node.router.prewarm
            eng.drain(5.0)
            print("prewarm:", {k: v for k, v in eng.stats.items() if v})
        node.close()


if __name__ == "__main__":
    main()
