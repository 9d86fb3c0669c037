"""Device idle share: 1 - union of device op intervals / traced window,
averaged over the cell's chips, over the leaf operations that the driver
hands over (``train_sharded.leaf_ops``), so that gaps inside the layer
scans count."""
from chipbench import readers


def read(ctx):
    return readers.idle_pct(ctx)
