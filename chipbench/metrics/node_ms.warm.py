"""Median time of the warm requests outside generation (the request's span less its serve.generate span), ms."""
from chipbench import program_spans


def read(run):
    return program_spans.span_ms(run, "warm", [program_spans.REQUEST_SPAN],
                                 minus=["serve.generate"])
