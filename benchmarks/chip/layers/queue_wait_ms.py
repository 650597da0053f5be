"""Serving layer: mean time a request waited in the server's queue before a
worker took it (``QueryResult.queued_s``), over the window's answers."""


def read(obs: dict):
    q = obs.get("queued_s")
    return sum(q) / len(q) * 1e3 if q else None
