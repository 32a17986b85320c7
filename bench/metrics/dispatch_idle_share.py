"""dispatch_idle_share: the share of the traced window in which the device
sat idle while the host was inside the program's ``fed.dispatch`` span
(and no span nested in it)."""


def read(record):
    tr = record.get("trace") or {}
    idle = tr.get("idle_by_span")
    if idle is None:
        return None
    return 100.0 * idle.get("fed.dispatch", 0.0) / tr["window_s"]
