"""Kernel ``kernels/flash_decode.py`` (``flash_decode_paged_pallas``): the
least time the chip could take for the window's paged decode attention
over the live contexts (the larger of its FLOPs over the bf16 peak and its
K/V bytes over HBM bandwidth, ``flops.paged_decode_cost``), over the
kernel's summed device time in the trace.  Moves ``output_tok_s``."""
import flops

KERNEL = "flash_decode_paged_pallas"


def read(run):
    if run.trace is None:
        return None
    spent, calls = run.trace.op_seconds(KERNEL)
    contexts = [c for t in run.window.ticks for c in t.decode]
    if not calls or spent <= 0 or not contexts:
        return None
    f, b = flops.paged_decode_cost(run.cell.model, contexts)
    least, bound = flops.least_time(f, b, run.peak)
    print(f"{KERNEL}: {calls} calls, {spent:.6f} s on the device, least "
          f"{least:.6f} s ({bound}-bound)", flush=True)
    return 100.0 * least / spent
