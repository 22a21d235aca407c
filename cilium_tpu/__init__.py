"""cilium_tpu — a TPU-native policy-verdict framework.

A from-scratch re-design of Cilium's capability surface (reference:
cilium v1.2.90) for TPU hardware:

- host control plane owning labels, security identities, rules and IP caches
  (reference: pkg/labels, pkg/identity, pkg/policy, pkg/ipcache);
- a *policy compiler* lowering the rule repository into dense device arrays
  (selector bitmaps, L4 tables, CIDR bit-tries, L7 DFA tables);
- a jit/pjit *verdict engine* evaluating batches of flow tuples on TPU
  (replaces the eBPF per-packet path bpf/lib/policy.h);
- a verdict-cache / enforcement front-end (the pkg/maps/policymap
  equivalent) consumed by datapath front-ends;
- endpoint lifecycle, kvstore-backed distribution, REST-ish API, CLI and
  observability around it.

Nothing in here is a port: the architecture is JAX/XLA-first (static
shapes, functional transforms, sharding via jax.sharding.Mesh).
"""

__version__ = "0.1.0"
