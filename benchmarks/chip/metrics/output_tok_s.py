"""Tokens delivered to clients inside the window, over its seconds."""
from benchmarks.chip import e2e


def read(view):
    return e2e.output_tok_s(view.run["requests"], view.run["seconds"])
