"""Programs the substrate built and compiled (or loaded from the
persistent cache) inside the window: the program's ``substrate.compile``
spans; a warm window has none."""
from bench.metrics._spans import span_count


def read(run):
    return span_count(run, "substrate.compile")
