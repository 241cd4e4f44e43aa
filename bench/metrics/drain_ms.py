"""Host time per round spent draining the round block's taps into the
recorder: the program's ``drain`` spans inside the window, per round."""


def read(r):
    spans = [e["dur"] for e in r.window_spans if e["name"] == "drain"]
    if not spans or not r.window_rounds:
        return None
    return sum(spans) / 1e3 / r.window_rounds
