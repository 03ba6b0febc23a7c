"""Model step, decode: model FLOPs of every decode step in the window
(2 x layer parameters x live rows, the logits head for each live row,
attention over each row's context) over the steps' host-clock time, as
a share of the chip's bf16 peak."""

from harness import counts


def read(run):
    return counts.mfu(run, "decode")
