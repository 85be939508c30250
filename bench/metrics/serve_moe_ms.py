"""serve_moe_ms (ms): device time under the ``moe`` scope (an expert
layer's router, one-hot dispatch, held experts and shared experts) per
execution of the decode step, each instant to the innermost operation,
from the trace of a ``serve_bank`` window (``bench/layer_scopes.py``).
None where no operation carries the scope."""
from bench import layer_scopes


def read(rec):
    return layer_scopes.per_step_ms(rec, "moe")
