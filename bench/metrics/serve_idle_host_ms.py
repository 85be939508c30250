"""serve_idle_host_ms (ms): device idle time per engine step inside the
engine's host loop, the spans ``serve.admit``, ``serve.dispatch`` and
``serve.bookkeep`` of ``ServeEngine.run``, from the trace of a
``serve_bank`` window (``bench/spans.py``).  None where the program writes
no such spans."""
from bench import spans


HOST_LOOP = ("serve.admit", "serve.dispatch", "serve.bookkeep")


def read(rec):
    return spans.idle_per_step_ms(spans.of_run(rec), HOST_LOOP)
