"""Host time per sort job laying the job's input over the chips: the
program's ``substrate.place`` spans (one ``jax.device_put`` to the
machine-axis sharding, waited for) inside the window, over the jobs,
in ms."""
from bench.metrics._spans import span_ms


def read(run):
    return span_ms(run, "substrate.place")
