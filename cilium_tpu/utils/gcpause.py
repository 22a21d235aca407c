"""The cyclic garbage collector held off for a bulk import.

An import that builds hundreds of thousands of long-lived objects (a
100k-rule policy and the regeneration it triggers, a remote cluster's
identities and ipcache entries) triggers generation-2 collections, and
each walks every object the process holds: on a node holding tens of
millions, seconds apiece, to find almost nothing to free, since what
the import allocates is kept. ``paused()`` turns the collector off for
the import. Reference counting still frees every acyclic object
meanwhile; cycles made inside wait for the first collection after.

The collector is process-wide, so is the pause: sections nest and may
run on several threads, and the collector comes back on when the last
one ends, unless it was off before the first began."""

from __future__ import annotations

import contextlib
import gc
import threading

_lock = threading.Lock()
_depth = 0
_was_enabled = False


@contextlib.contextmanager
def paused():
    global _depth, _was_enabled
    with _lock:
        if _depth == 0:
            _was_enabled = gc.isenabled()
            gc.disable()
        _depth += 1
    try:
        yield
    finally:
        with _lock:
            _depth -= 1
            if _depth == 0 and _was_enabled:
                gc.enable()
