"""Decode tokens made in the window over decode steps times slots: the
mean share of the slots that decode in a decode step."""


def read(run):
    d = run.data
    t = d.get("tracker")
    if t is None or not t.decode_steps:
        return None
    return t.decode_tokens / (t.decode_steps * d["n_slots"])
