"""Median time of the cold requests in the upload ring's issue of its jobs (spice.upload.issue spans), ms."""
from chipbench import program_spans


def read(run):
    return program_spans.span_ms(run, "cold", ["spice.upload.issue"])
