"""Kernel ``kernels/softmax_topk.py`` (``softmax_topk_pallas``): the least
time the chip could take to read the float32 logits once (every row of
each decode step's [slots, vocab] input, and the [1, vocab] row of each
first token), over the kernel's summed device time in the trace.  Memory
bound by construction.  Moves ``output_tok_s``."""
import flops

KERNEL = "softmax_topk_pallas"


def read(run):
    if run.trace is None:
        return None
    spent, calls = run.trace.op_seconds(KERNEL)
    w = run.window
    steps = w.counters1["decode_steps"] - w.counters0["decode_steps"]
    firsts = sum(t.first_tokens for t in w.ticks)
    if not calls or spent <= 0 or steps + firsts <= 0:
        return None
    vocab = run.cell.model["vocab_size"]
    nbytes = (flops.softmax_topk_bytes(run.cell.serving["slots"], vocab)
              * steps + flops.softmax_topk_bytes(1, vocab) * firsts)
    least, bound = flops.least_time(0.0, nbytes, run.peak)
    print(f"{KERNEL}: {calls} calls for {steps} decode steps and {firsts} "
          f"first tokens, {spent:.6f} s on the device, least {least:.6f} s "
          f"({bound}-bound)", flush=True)
    return 100.0 * least / spent
