"""Tokens of every stage step completed in the window over the whole window,
which ends when the last step's outputs are ready (host clock)."""


def read(m):
    return m.steps * m.tokens_per_step / m.window_s
