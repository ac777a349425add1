"""Median time of the cold requests in the uploader's host-to-HBM puts (spice.upload.put spans), ms."""
from chipbench import program_spans


def read(run):
    return program_spans.span_ms(run, "cold", ["spice.upload.put"])
