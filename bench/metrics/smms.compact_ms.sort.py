"""Host time per sort job concatenating each machine's filled prefix
into the answer: the program's ``smms.compact`` spans inside the
window, over the jobs, in ms."""
from bench.metrics._spans import span_ms


def read(run):
    return span_ms(run, "smms.compact")
