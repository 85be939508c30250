"""serve_adapter_ms (ms): device time in the ``tri_lora`` scope (the grouped
tri-LoRA delta of the adapter bank) per execution of the decode step, from
the trace of a ``serve_bank`` window (``bench/spans.py``).  None where no
operation carries the scope."""
from bench import spans


def read(rec):
    return spans.scope_per_step_ms(spans.of_run(rec), "tri_lora")
