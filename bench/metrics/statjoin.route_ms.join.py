"""Host time per join call turning StatJoin's plan into device
operands (routing index lists, fragments and their upload): the
program's ``statjoin.route`` spans inside the window, over the calls,
in ms."""
from bench.metrics._spans import span_ms


def read(run):
    return span_ms(run, "statjoin.route")
