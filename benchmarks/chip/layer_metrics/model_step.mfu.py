"""Model step (``serving/engine.py``, ``models/``): model FLOPs of the
tokens the window processed over the window's length times the chip's
bf16 peak.  FLOPs count the matrix parameters and the attention at each
token's context for every decode row and for every prefill chunk the
scheduler recorded (its ``prefill_chunk`` spans), plus the LM head once per
sampled token (``flops.py``).  Moves ``output_tok_s``."""
import flops


def read(run):
    w, m = run.window, run.cell.model
    total = 0.0
    for t in w.ticks:
        if t.decode:
            total += flops.decode_flops(m, t.decode)
        total += t.first_tokens * flops.head_flops(m)
    for off, width in w.chunks:
        total += flops.prefill_flops(m, off, width)
    if total <= 0 or w.seconds <= 0:
        return None
    return 100.0 * total / (w.seconds * run.peak["bf16_flops_per_s"])
