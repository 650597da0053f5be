"""Topology: seconds ``engine.startup()`` spent building the edge lists
(its own ``edge_list_build_s`` breakdown)."""


def read(obs: dict):
    return (obs.get("startup") or {}).get("edge_list_build_s")
