"""The Stokes3D launch ``compute_dV``'s share of the HBM roofline
(residuals and velocity increments: 15 field operands and 6
results, each counted once) over the chip's HBM bandwidth times the
launch's device time in the traced window (``yardstick/launch_share.py``)."""
from yardstick import launch_share


def read(ctx):
    return launch_share.read(ctx, "compute_dV")
