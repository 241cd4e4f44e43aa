"""Seconds of set-up in the program's ``stage_data`` span: padding the
client stacks and moving them to the device."""


def read(r):
    spans = [e["dur"] for e in r.setup_spans if e["name"] == "stage_data"]
    return sum(spans) / 1e6 if spans else None
