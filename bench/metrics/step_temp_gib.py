"""Temporaries of the compiled timed step (memory_analysis), the part of a
step's memory that parameters and state do not explain; the largest over
the cell's chips."""


def read(m):
    return max(m.temp_bytes) / 2 ** 30
