"""serve_step_ms (ms): device time per execution of the program that ran
most often in a ``serve_bank`` window (the engine's decode step), from the
trace's program executions."""


def read(rec):
    if rec.get("driver") != "serve_bank":
        return None
    top = rec["trace"]["top_module"]
    if not top or not top["executions"]:
        return None
    return 1e3 * top["device_s"] / top["executions"]
