"""Plain numpy references for the benchmark's correctness checks.

Nothing here imports the system under test: each reference recomputes
the answer from the inputs alone, and each ``compare_*`` counts how far
an answer the system produced departs from it.
"""
