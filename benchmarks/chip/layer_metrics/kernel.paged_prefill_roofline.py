"""Kernel ``kernels/flash_attention.py`` (``flash_attention_paged_pallas``):
the least time the chip could take for the window's paged prefill
attention, chunk by chunk at its offset as the scheduler recorded it
(its ``prefill_chunk`` spans; the larger of its causal FLOPs
over the bf16 peak and its K/V bytes over HBM bandwidth,
``flops.paged_prefill_cost``), over the kernel's summed device time in the
trace.  Moves ``output_tok_s``."""
import flops

KERNEL = "flash_attention_paged_pallas"


def read(run):
    if run.trace is None:
        return None
    spent, calls = run.trace.op_seconds(KERNEL)
    chunks = run.window.chunks
    if not calls or spent <= 0 or not chunks:
        return None
    f = b = 0.0
    for off, width in chunks:
        cf, cb = flops.paged_prefill_cost(run.cell.model, off, width)
        f, b = f + cf, b + cb
    least, bound = flops.least_time(f, b, run.peak)
    print(f"{KERNEL}: {calls} calls, {len(chunks)} chunks, {spent:.6f} s on "
          f"the device, least {least:.6f} s ({bound}-bound)", flush=True)
    return 100.0 * least / spent
