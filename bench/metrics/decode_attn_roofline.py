"""Kernels, decode: the decode attention kernel's least time (the larger
of FLOPs over peak and bytes over HBM bandwidth, per call, from its
count in bench/kernels/) summed over the window's calls, over the
kernel's device time in the trace."""

from harness import counts


def read(run):
    return counts.roofline(run, "decode")
