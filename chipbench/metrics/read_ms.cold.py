"""Median time of the cold requests in the reader's storage ops (spice.read spans), ms."""
from chipbench import program_spans


def read(run):
    return program_spans.span_ms(run, "cold", ["spice.read"])
