"""Median time of the warm requests in dispatching the embed, layer and head programs (serve.dispatch spans), ms."""
from chipbench import program_spans


def read(run):
    return program_spans.span_ms(run, "warm", ["serve.dispatch"])
