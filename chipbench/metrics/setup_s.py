"""setup_s: seconds from the start of `run.py` until the window opens:
imports, reaching the chip, graph, weights, compile and admission, and the
warm-up calls (which compile, or load from the persistent cache)."""


def read(rec):
    return rec.setup_s
