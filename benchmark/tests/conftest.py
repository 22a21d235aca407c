"""The benchmark's own tests run on the CPU, at tiny sizes."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Each cell cut to a size a CPU test holds; every width and share of
# the traffic mix stays as the cell has it.
TINY = {
    "node-5k": {"local_endpoints": 12, "remote_pods": 600, "services": 60,
                "ingress_rules": 60, "prefilter_prefixes": 500, "egress_rules": 4},
    "l7-mesh": {"local_endpoints": 8, "remote_pods": 120, "services": 60,
                "ingress_rules": 60},
}
TINY_TRAFFIC = {
    "node-5k.newflows-sat": {"rate": 20000},
    "l7-mesh.http-sat": {"rate": 3000, "bank_size": 500},
}


def tiny(cell: str) -> dict:
    return {"config": TINY[cell.split(".")[0]], "traffic": TINY_TRAFFIC[cell]}
