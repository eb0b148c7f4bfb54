"""A planted architecture for the tests of the dispatch: ``dense_gqa``
with the MLP's down projection drawn from another key and every matmul
counted twice.  Its verdict and its counts differ from ``dense_gqa``'s only
if the harness takes both from the module the configuration names."""
import os

import reference

_DENSE = os.path.join(os.path.dirname(os.path.abspath(reference.__file__)),
                      "references", "dense_gqa.py")
with open(_DENSE) as _f:
    exec(compile(_f.read(), _DENSE, "exec"))     # dense_gqa's definitions

_dense_layer_weights = _layer_weights  # noqa: F821
_dense_matmul_flops = matmul_flops  # noqa: F821


def _layer_weights(key, m, dtype):
    w = _dense_layer_weights(key, m, dtype)
    k = jax.random.fold_in(key, 7)  # noqa: F821
    w["w_down"] = _normal(k, w["w_down"].shape,  # noqa: F821
                          _fan(m["d_ff"]), dtype)  # noqa: F821
    return w


def matmul_flops(m):
    return 2 * _dense_matmul_flops(m)
