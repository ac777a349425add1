"""Median time of the cold requests in handing tensors to the upload ring (spice.ring_wait spans), ms."""
from chipbench import program_spans


def read(run):
    return program_spans.span_ms(run, "cold", ["spice.ring_wait"])
