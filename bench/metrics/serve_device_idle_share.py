"""serve_device_idle_share (%): 1 - (union of device operation intervals)
/ window, from the trace of a ``serve_bank`` window, averaged over chips.
The host's bookkeeping between decode steps shows here."""


def read(rec):
    if rec.get("driver") != "serve_bank":
        return None
    tr = rec["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
