"""Seconds of the set-up's clustermesh import: every remote cluster
added to the node's ``ClusterNode`` and pumped until no event is left
(host clock; the harness's ``remote_import`` step)."""


def read(r):
    return r.setup_steps.get("remote_import")
