"""Seconds of the set-up's daemon calls: boot, endpoint_add, identity
allocation, ipcache upserts, prefilter insert, policy_add (host clock)."""


def read(r):
    return r.world_build_s
