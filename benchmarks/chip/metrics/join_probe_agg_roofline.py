"""The join_probe_agg kernel's share of its roofline: the least time the chip
could take for its calls in the window (``peaks``: bytes and operations
from each call's shapes, against the HBM and compute peaks) over their
traced time."""


def read(run):
    return run.kernel_roofline_pct("join_probe_agg")
