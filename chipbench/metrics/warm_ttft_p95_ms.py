"""95th percentile of client-side time to first token of warm hits, ms."""
from chipbench import readers


def read(run):
    return readers.ttft_ms(run, "warm", 95)
