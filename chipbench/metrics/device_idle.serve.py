"""Device idle share: 1 - union of device op intervals / traced window."""
from chipbench import readers


def read(ctx):
    return readers.idle_pct(ctx)
