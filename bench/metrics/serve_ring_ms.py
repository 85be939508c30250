"""serve_ring_ms (ms): device time in the ``kv_ring`` scope (the ring write,
its validity mask, and every operation whose result has the ring's shape)
per execution of the decode step, from the trace of a ``serve_bank``
window (``bench/spans.py``).  None where no operation carries the scope."""
from bench import spans


def read(rec):
    return spans.scope_per_step_ms(spans.of_run(rec), "kv_ring")
