"""serve_mla_ms (ms): device time under the ``mla`` scope (an MLA attention
sub-block: its projections, the latent ring's write, the absorbed
attention dots, the output projection and their adapters) per execution
of the decode step, each instant to the innermost operation, from the
trace of a ``serve_bank`` window (``bench/layer_scopes.py``).  None where
no operation carries the scope."""
from bench import layer_scopes


def read(rec):
    return layer_scopes.per_step_ms(rec, "mla")
