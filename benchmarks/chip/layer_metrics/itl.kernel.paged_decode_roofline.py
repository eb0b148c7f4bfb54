"""``kernel.paged_decode_roofline``'s reading, in the cells judged on their
gaps and not on their throughput.  Moves ``itl_p50_ms``: there the typical
gap is one decode tick, which runs this kernel in every layer."""
import harness

read = harness.layer_reader("kernel.paged_decode_roofline")
