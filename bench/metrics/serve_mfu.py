"""serve_mfu (%): the forward work every request of a ``serve_bank`` window
needs (``counts.serve_request_flops``: each prompt and generated token
through the base, the adapters and attention over its own context, logits
where a token is emitted), over the window's host-clock seconds, the chips
and the bf16 peak.  The same work is counted whatever computes it."""


def read(rec):
    if rec.get("driver") != "serve_bank":
        return None
    return 100.0 * rec["flops"] / (rec["window_s"] * rec["chips"]
                                   * rec["peak"]["bf16_flops_per_s"])
