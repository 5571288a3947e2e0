"""Share of the chips' busy seconds in collective operations (%): the
all-to-all row exchange and the all-gather of a type expansion, and any other
collective the compiler put in, read from the HLO of each operation in the
profile (``benchmark/dist_chain.py``), summed over the four chips."""
from benchmark import xplane
from benchmark.dist_chain import device_intervals


def read(run):
    devs = device_intervals(run)
    busy = sum(xplane.total(b) for b, _c in devs)
    if busy <= 0:
        return None
    return 100.0 * sum(xplane.total(c) for _b, c in devs) / busy
