"""The one traffic generator: turns a mix's data file and a seed into
prompts and the order in which a closed-loop client sends them.

A mix file (``chipbench/traffic/<name>.json``) declares:

  clients          callers, each sending its next request when the last
                   one answered (closed loop; 1 is all the harness drives)
  prompt_len       token ids per prompt
  prompt_pool      distinct prompts made from the seed
  max_new_tokens   tokens each request asks for
  qos              the requests' QoS class ("latency", "standard", "batch")
  functions        distinct fine-tunes the mix invokes, in round-robin
  keep_alive_s     warm keep-alive of the node (0: every request restores)
  warm_in_setup    whether set-up restores every function, so the window
                   sees warm hits only
  expect           what every request in the window must be: "cold"
                   (a restore it owns, none joined) or "warm"

Every seed gets the same prompts' sizes and the same function order; the
seed picks the token ids and the order in which the pool is cycled.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, Tuple

import numpy as np

KEYS = ("clients", "prompt_len", "prompt_pool", "max_new_tokens", "qos",
        "functions", "keep_alive_s", "warm_in_setup", "expect")


def validate(mix: Dict) -> None:
    missing = [k for k in KEYS if k not in mix]
    if missing:
        raise ValueError(f"traffic mix lacks {missing}")
    if mix["clients"] != 1:
        raise ValueError("the harness drives one closed-loop client")
    if mix["expect"] not in ("cold", "warm"):
        raise ValueError(f"expect must be cold or warm, not {mix['expect']!r}")
    if mix["expect"] == "cold" and (mix["keep_alive_s"] or mix["warm_in_setup"]):
        raise ValueError("a cold mix keeps nothing warm")
    if mix["expect"] == "warm" and not (mix["keep_alive_s"] and mix["warm_in_setup"]):
        raise ValueError("a warm mix keeps its functions warm from set-up on")
    for k in ("prompt_len", "prompt_pool", "max_new_tokens", "functions"):
        if int(mix[k]) < 1:
            raise ValueError(f"{k} must be at least 1")


def seed_key(seed: int) -> int:
    """A 31-bit key for JAX's PRNG from any whole-number seed."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


@dataclass
class Traffic:
    mix: Dict
    seed: int
    vocab: int

    def __post_init__(self):
        validate(self.mix)
        rng = np.random.default_rng([self.seed, 1])
        self.prompts = rng.integers(
            0, self.vocab, (self.mix["prompt_pool"], self.mix["prompt_len"]),
            dtype=np.int32,
        )
        self._rng = rng

    def requests(self) -> Iterator[Tuple[int, int]]:
        """Endless (function index, prompt index) pairs: functions in
        round-robin, the prompt pool cycled in a fresh seeded order each
        pass."""
        n_fn, pool = self.mix["functions"], self.mix["prompt_pool"]
        i = 0
        while True:
            for p in self._rng.permutation(pool):
                yield i % n_fn, int(p)
                i += 1
