"""Share of its ICI roofline that SMMS's Round-3 all_to_all reaches:
the bytes each chip sends to the other chips (``bench/metrics/_ici.py``)
over the chip's whole ICI peak, against the all_to_all ops' device time
per chip, in percent."""
from bench.metrics import _ici


def read(run):
    return _ici.roofline(run)
