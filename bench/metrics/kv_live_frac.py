"""Over the window's steps, pages holding live tokens over pages the
slots hold (a request holds pages for its whole prompt and answer from
admission)."""


def read(run):
    t = run.data.get("tracker")
    if t is None or not t.detail or not t.kv_reserved:
        return None
    return t.kv_live / t.kv_reserved
