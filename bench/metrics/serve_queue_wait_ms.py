"""serve_queue_wait_ms (ms): mean wait from a request's due time to the
start of its batch, from the serving engine's own ``serve.queue_wait_s``
counter over the requests its batches ran in the window."""


def read(run):
    f = run.facts
    if not f.get("batched_requests"):
        return None
    return 1e3 * f["queue_wait_s"] / f["batched_requests"]
