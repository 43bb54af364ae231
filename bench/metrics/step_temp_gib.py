"""Temporaries of the compiled timed step (memory_analysis), the part of a
step's memory that parameters and state do not explain."""


def read(m):
    return m.temp_bytes / 2 ** 30
