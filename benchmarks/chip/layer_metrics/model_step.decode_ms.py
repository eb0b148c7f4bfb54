"""Model step (``serving/engine.py``, ``models/``): device time of one run
of the jitted paged decode step (``jit_decode`` in the trace), averaged
over the window's runs.  Moves ``itl_p50_ms``: the typical gap between
tokens is one decode step."""


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_seconds("jit_decode")
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
