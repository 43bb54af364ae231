"""Share of the window's leaf operation time that no named region of the
step program claims: operations whose ``op_name`` path holds no scope, or
that have no ``op_name`` at all.  The coverage guard of the scope metrics:
new work that nobody named shows here."""
from bench import scopes


def read(m):
    return scopes.share(m, lambda names, _: not names)
