"""Output tokens delivered in the window over its length: every token
stamped by a step that started inside the window, over the time from the
start of the window's first step to the end of its last."""


def read(run):
    t = run.data.get("tracker")
    if t is None or not run.window_s:
        return None
    return t.tokens_in_window / run.window_s
