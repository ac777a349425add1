"""Share of the HBM roofline the overlay_patch kernel reached in the traced cold window, %."""
from chipbench import readers


def read(run):
    return readers.roofline(run, "overlay_patch")
