"""Host time per sort job copying SMMS's padded answer buffers (keys,
counts, values) from the device: the program's ``smms.fetch`` spans
inside the window, over the jobs, in ms."""
from bench.metrics._spans import span_ms


def read(run):
    return span_ms(run, "smms.fetch")
