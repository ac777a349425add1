"""End-to-end driver: a serverless node serving BATCHED requests across a
zoo of model functions with aggressive reclamation — every invocation after
an idle gap is a disk cold start, which Spice makes near-warm.

    PYTHONPATH=src python examples/serve_coldstart.py
"""
import tempfile
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import BaseImage
from repro.models import lm
from repro.serve.engine import ServerlessNode, layerwise_state

REQUESTS = [  # (function, prompt len) — a bursty multi-tenant trace
    ("chat-a", 8), ("chat-a", 8), ("code-b", 16), ("chat-a", 8),
    ("ssm-c", 8), ("code-b", 16), ("chat-a", 8), ("ssm-c", 8),
]


def main():
    node = ServerlessNode()
    with tempfile.TemporaryDirectory() as d:
        # three functions; two share one base image (a "Python+AI pool")
        base_cfg = get_config("qwen1.5-0.5b").reduced()
        base_params = lm.init_params(base_cfg, jax.random.PRNGKey(1))
        node.node_cache.put(
            BaseImage.from_state("pool-base", layerwise_state(base_cfg, base_params))
        )
        ft = jax.tree.map(lambda a: a, base_params)
        ft["final_norm"] = ft["final_norm"] * 1.01
        node.publish("chat-a", base_cfg, base_params, d, base_name="pool-base")
        node.publish("code-b", base_cfg, ft, d, base_name="pool-base")

        ssm_cfg = get_config("mamba2-780m").reduced()
        node.publish("ssm-c", ssm_cfg, lm.init_params(ssm_cfg, jax.random.PRNGKey(2)), d)

        cfgs = {"chat-a": base_cfg, "code-b": base_cfg, "ssm-c": ssm_cfg}
        # compile-cache warmup per arch
        for f, cfg in cfgs.items():
            node.invoke(f, np.ones((1, 4), np.int32), 2, cfg=cfg)

        print(f"{'req':>3} {'function':>8} {'start':>6} {'ttft_ms':>9} {'total_ms':>9}")
        for i, (fname, plen) in enumerate(REQUESTS):
            node.evict()  # aggressive reclamation: idle instances are freed
            prompt = np.tile(np.arange(1, plen + 1, dtype=np.int32), (2, 1))
            r = node.invoke(fname, prompt, max_new_tokens=4, cfg=cfgs[fname])
            print(f"{i:>3} {fname:>8} {'cold':>6} {r.ttft_s*1e3:9.2f} {r.total_s*1e3:9.2f}")

        print("\nnode cache:", node.node_cache.stats)
        print("buffer pool:", node.pool.stats)


if __name__ == "__main__":
    main()
