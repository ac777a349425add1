"""Median RestoreStats.total_s of the cold requests, ms."""
from chipbench import readers


def read(run):
    return readers.median_stat_ms(run, "cold", "restore_s")
