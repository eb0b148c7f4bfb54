"""Scheduler (``serving/scheduler.py``): mean share of the decode batch's
slots holding a live request, per decode step in the window, from the
engine's public counters (``Engine.report().occupancy`` times
``decode_steps``, before and after the window).  Moves ``output_tok_s``."""


def read(run):
    c0, c1 = run.window.counters0, run.window.counters1
    steps = c1["decode_steps"] - c0["decode_steps"]
    if steps <= 0:
        return None
    return 100.0 * (c1["occupancy_sum"] - c0["occupancy_sum"]) / steps
