"""Prefill model FLOPs of the cold window over the bf16 peak, %."""
from chipbench import readers


def read(run):
    return readers.mfu(run, "cold")
