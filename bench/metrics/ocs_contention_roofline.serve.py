"""The contention kernel's share of its roofline in the serving cells
that decode through the channel: the least time its logical work (from N,
K, bits, id_bits, max_rounds; one contention per FFN site of every tick)
needs on the chip, over the summed device time of its events."""

from bench.lib import counts


def read(run, cell):
    return counts.kernel_roofline(run, cell)
