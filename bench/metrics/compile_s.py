"""Seconds of set-up in the program's ``compile`` spans: compiling the
round blocks, or loading them from the compilation cache."""


def read(r):
    spans = [e["dur"] for e in r.setup_spans if e["name"] == "compile"]
    return sum(spans) / 1e6 if spans else None
