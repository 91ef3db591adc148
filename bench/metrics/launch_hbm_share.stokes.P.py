"""The Stokes3D launch ``compute_P``'s share of the HBM roofline
(pressure and divergence: 6 field operands and 2 results, each counted once) over the chip's HBM bandwidth times the
launch's device time in the traced window (``yardstick/launch_share.py``)."""
from yardstick import launch_share


def read(ctx):
    return launch_share.read(ctx, "compute_P")
