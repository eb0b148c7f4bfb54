"""Scheduler (``serving/scheduler.py``): share of the prompt tokens of the
requests admitted in the window (the scheduler's ``queued`` spans, which
end at admission) that the prefix cache served (the pool's
``tokens_reused`` counter), so their prefill was skipped.  Moves
``itl_p95_ms``: every prefill chunk spared shortens a tick that carries
one."""


def read(run):
    w = run.window
    admitted = sum(w.admitted)
    if admitted <= 0:
        return None
    reused = w.counters1["tokens_reused"] - w.counters0["tokens_reused"]
    return 100.0 * reused / admitted
