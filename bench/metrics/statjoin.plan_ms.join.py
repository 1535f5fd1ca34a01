"""Host time per join call in StatJoin's planner (keys to mapping
rectangles and machines): the program's ``statjoin.plan`` spans inside
the window, over the calls, in ms."""
from bench.metrics._spans import span_ms


def read(run):
    return span_ms(run, "statjoin.plan")
