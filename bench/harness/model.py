"""What every architecture module (``bench/arch/<arch>.py``) shares to
make its weights.

The weights are the benchmark's own: an architecture's ``make_params``
draws every leaf from ``--seed`` in one jitted call on the device, in the
dtype they are served in, laid out as the program's parameter tree
expects.  The reference reads the same arrays; nothing the program makes
is handed to it.
"""

from __future__ import annotations


def key_for(seed: int):
    """A PRNG key from any whole seed (the driver's exceed 32 bits)."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)
