"""serve_idle_sync_ms (ms): device idle time per engine step inside
``serve.sync``, the engine's read of the step's tokens to the host, from
the trace of a ``serve_bank`` window (``bench/spans.py``).  None where the
program writes no such span."""
from bench import spans


def read(rec):
    return spans.idle_per_step_ms(spans.of_run(rec), ("serve.sync",))
