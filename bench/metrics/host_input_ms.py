"""Mean host time per window step inside the benchmark's input span:
building the step's batch from the token pool and putting it on the device."""


def read(m):
    if not m.input_s:
        return None
    return 1000.0 * sum(m.input_s) / len(m.input_s)
