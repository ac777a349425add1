"""Share of the traced warm window with no operation on the chip, %."""
from chipbench import readers


def read(run):
    return readers.idle_share(run, "warm")
