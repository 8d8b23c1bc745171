"""knn_refresh_share (%): the program's ``train.refresh`` spans inside the
window (each ends in the graph's ``device_get`` and host CSR packing),
over the window."""


def read(run):
    if run.tracer is None or not run.window_s:
        return None
    s = run.span_seconds("train.refresh")
    if s <= 0:
        return None
    return 100.0 * s / run.window_s
