"""Quickstart: snapshot a model function to a JIF, tear everything down,
and cold-start it from disk in milliseconds.

    PYTHONPATH=src python examples/quickstart.py
"""
import tempfile

import jax
import numpy as np

from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import ServerlessNode

def main():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))

    node = ServerlessNode()
    with tempfile.TemporaryDirectory() as d:
        print("== publish: offline JIF preparation (trace + relocate + trim)")
        spec = node.publish("hello-fn", cfg, params, d)
        print(f"   wrote {spec.jif_path}")

        prompt = np.array([[11, 12, 13, 14]], dtype=np.int32)

        print("== warm up the compile cache (restored via keys, not re-trace)")
        node.invoke("hello-fn", prompt, max_new_tokens=4, cfg=cfg)
        node.evict()

        print("== COLD start: restore from disk, overlap restore & execute")
        r = node.invoke("hello-fn", prompt, max_new_tokens=8, cfg=cfg)
        print(f"   tokens: {r.tokens[0].tolist()}")
        print(f"   ttft:   {r.ttft_s*1e3:.2f} ms   total: {r.total_s*1e3:.2f} ms")
        print(f"   restore stats: {r.stats}")


if __name__ == "__main__":
    main()
