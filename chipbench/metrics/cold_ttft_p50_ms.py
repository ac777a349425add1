"""Median client-side time to first token of cold requests, ms."""
from chipbench import readers


def read(run):
    return readers.ttft_ms(run, "cold", 50)
