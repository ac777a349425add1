"""Median time of the cold requests that generation waited for restored parameters (serve.resolve spans), ms."""
from chipbench import program_spans


def read(run):
    return program_spans.span_ms(run, "cold", ["serve.resolve"])
