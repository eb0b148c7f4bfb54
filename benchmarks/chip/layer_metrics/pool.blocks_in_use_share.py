"""Pool (``serving/paged.py``): mean over the window's ticks of the share
of usable KV blocks held by live sequences, neither free nor parked in the
prefix cache, from the ``blocks`` counter the scheduler records after each
tick (free and cached blocks).  Moves ``output_tok_s``."""


def read(run):
    w = run.window
    if not w.blocks or w.usable_blocks <= 0:
        return None
    used = [w.usable_blocks - free - cached for free, cached in w.blocks]
    return 100.0 * sum(used) / len(used) / w.usable_blocks
