"""Median time of the cold requests in the upload ring's waits for issued jobs to land (spice.upload.land spans), ms."""
from chipbench import program_spans


def read(run):
    return program_spans.span_ms(run, "cold", ["spice.upload.land"])
