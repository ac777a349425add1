"""Prefill model FLOPs of the warm window over the bf16 peak, %."""
from chipbench import readers


def read(run):
    return readers.mfu(run, "warm")
