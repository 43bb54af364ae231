"""Mean host time per window step in the benchmark's dispatch span: for a
cell that runs every stage, ``StageExecutor.tick``, which puts each stage's
inputs on its chip and dispatches its step."""


def read(m):
    if not m.dispatch_s:
        return None
    return 1000.0 * sum(m.dispatch_s) / len(m.dispatch_s)
