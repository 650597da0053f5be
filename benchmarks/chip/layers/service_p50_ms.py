"""Executor and read path: median time a worker spent on a request
(``QueryResult.service_s``), over the window's answers."""

import statistics


def read(obs: dict):
    s = obs.get("service_s")
    return statistics.median(s) * 1e3 if s else None
