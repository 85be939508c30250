"""serve_emit_share (%): slot-steps that emitted a token over slots x steps
of the engine's ``run()`` in a ``serve_bank`` window, from the counters
``ServeEngine.stats`` that ``serve.run`` carries in the trace
(``bench/spans.py``).  None where the trace holds no counters."""
from bench import spans


def read(rec):
    return spans.slot_step_share(spans.of_run(rec), "slot_steps_emit")
