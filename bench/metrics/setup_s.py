"""Process start to the first timed step: imports, weights and tables made on
the device, compilation or the compile cache, and the checked first steps."""


def read(m):
    return m.setup_s
