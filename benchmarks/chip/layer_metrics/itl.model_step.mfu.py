"""``model_step.mfu``'s reading, in the cells judged on their gaps and not
on their throughput.  Moves ``itl_p50_ms``: there the typical gap is one
decode tick of the whole model step."""
import harness

read = harness.layer_reader("model_step.mfu")
