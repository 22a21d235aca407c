"""Seconds of the set-up's policy import: ``Daemon.policy_add`` of the
whole rule set, with the regeneration of every local endpoint it
triggers (host clock; the harness's ``policy_add`` step)."""


def read(r):
    return r.setup_steps.get("policy_add")
