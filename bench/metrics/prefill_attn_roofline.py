"""Kernels, prefill: as decode_attn_roofline, for the prefill chunk
attention kernels (first chunk and later chunks)."""

from harness import counts


def read(run):
    return counts.roofline(run, "prefill")
