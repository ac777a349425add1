"""Median RestoreStats.upload_s (host to HBM transfers and patches) of the cold requests, ms."""
from chipbench import readers


def read(run):
    return readers.median_stat_ms(run, "cold", "upload_s")
