"""Host time per join call in StatJoin's statistics (the per-key
multiplicities of both tables): the program's ``statjoin.statistics``
spans inside the window, over the calls, in ms."""
from bench.metrics._spans import span_ms


def read(run):
    return span_ms(run, "statjoin.statistics")
