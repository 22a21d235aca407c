"""Every shape a cell warms compiles for a TPU v5e.

For one chip of a described ``v5e:2x2`` topology: the verdict programs
each flow cell dispatches (v4 ingress with the prefilter stage, v4
egress, v6 at the 16-level walk) at every rung of the dispatch ladder,
and the L7 cell's DFA walks (stride-2 and single-byte) at its lane and
length rungs, at the table widths the cells build (from a full-size
build of each world: ``benchmark/run.py``'s ``phase=tables`` line).
Nothing runs: a compile
that passes says the chip's compiler takes the program and that it
fits, not that it is right or fast.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

RUNGS = (1024, 2048, 4096, 8192)     # contracts.BUCKET_LADDER

# node-5k at full size (110 endpoints, 150k pods, 10k identities,
# 10k rules, 50k prefilter prefixes)
NODE5K = dict(endpoints=110, rows=10240, pf_sub=43828, ip_sub=9, v6_nodes=614, v6_common=2,
              cols={0: 160, 1: 128}, words={0: 10, 1: 8})
# l7-mesh at full size (110 endpoints, 2k pods, 1k identities)
L7MESH = dict(endpoints=110, rows=1024, pf_sub=1, ip_sub=3, cols={0: 224}, words={0: 14})
# fused DFA states of the four API shapes under a per-service path
# segment: three walk the stride-2 pair table; the fourth's pair table
# would pass PAIR_TABLE_CAP_ELEMS, so it walks one byte a step
L7_STATES = (98, 108, 118)
L7_STATES_FUSED = (136,)
L7_LANES = (512, 4096)               # lane rungs 32-512 requests x 2 fields reach
L7_LENS = (16, 32, 64, 128, 256)     # length rungs; 256 is the path field cap


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _a(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _policymap(a, w, direction):
    from cilium_tpu.ops.lookup import PolicymapTables

    c = w["cols"][direction]
    return PolicymapTables(
        col_ep=a((c,), jnp.int32), col_port=a((c,), jnp.int32),
        col_proto=a((c,), jnp.int32), col_is_l3=a((c,), jnp.bool_),
        id_bits=a((w["rows"], w["words"][direction]), jnp.uint32))


def _v4(a, w, direction, rung):
    from cilium_tpu.datapath.pipeline import WideDatapathTables, process_flows_wide

    i32 = jnp.int32
    t = WideDatapathTables(
        pf_root_info=a((65536,), i32), pf_root_child=a((65536,), i32),
        pf_sub_child=a((w["pf_sub"], 256), i32), pf_sub_info=a((w["pf_sub"], 256), i32),
        ip_root_info=a((65536,), i32), ip_root_child=a((65536,), i32),
        ip_sub_child=a((1, 65536), i32), ip_sub_info=a((w["ip_sub"], 65536), i32),
        merged_root_info=a((1,), i32), merged_root_child=a((1,), i32),
        merged_sub_child=a((1, 1), i32), merged_sub_info=a((1, 1), i32),
        world_row=a((), i32), policymap=_policymap(a, w, direction))
    f = a((rung,), i32)
    return process_flows_wide.lower(t, a((rung,), jnp.uint32), f, f, f,
                                    ep_count=w["endpoints"], prefilter=direction == 0,
                                    row_override=None)


def _v6(a, w, direction, rung):
    from cilium_tpu.datapath.pipeline import DatapathTables, process_flows

    i32 = jnp.int32
    n = w["v6_nodes"]
    t = DatapathTables(
        pf_child=a((1, 256), i32), pf_info=a((1, 256), i32), pf_common=a((0,), i32),
        ip_child=a((n, 256), i32), ip_info=a((n, 256), i32),
        ip_common=a((w["v6_common"],), i32),
        merged_child=a((1, 256), i32), merged_info=a((1, 256), i32),
        merged_common=a((0,), i32), world_row=a((), i32),
        policymap=_policymap(a, w, direction))
    f = a((rung,), i32)
    return process_flows.lower(t, a((rung, 16), i32), f, f, f, ep_count=w["endpoints"],
                               levels=16, prefilter=False, fused=False, row_override=None)


def _dfa(a, states, lanes, length):
    from cilium_tpu.ops.dfa import PAIR_ALPHA, dfa_match_batch_pair

    return dfa_match_batch_pair.lower(
        a((states, PAIR_ALPHA * PAIR_ALPHA), jnp.int32),
        a((states,), jnp.uint32), a((states,), jnp.uint32),
        a((lanes,), jnp.int32), a((lanes, length), jnp.uint8),
        a((lanes,), jnp.int32), max_len=length)


def _dfa_fused(a, states, lanes, length):
    from cilium_tpu.ops.dfa import dfa_match_batch_fused

    return dfa_match_batch_fused.lower(
        a((states, 256), jnp.int32),
        a((states,), jnp.uint32), a((states,), jnp.uint32),
        a((lanes,), jnp.int32), a((lanes, length), jnp.uint8),
        a((lanes,), jnp.int32), max_len=length)


CASES = (
    [(f"node-5k/v4-ingress/{r}", lambda a, r=r: _v4(a, NODE5K, 0, r)) for r in RUNGS]
    + [(f"node-5k/v4-egress/{r}", lambda a, r=r: _v4(a, NODE5K, 1, r)) for r in RUNGS]
    + [(f"node-5k/v6-ingress/{r}", lambda a, r=r: _v6(a, NODE5K, 0, r)) for r in RUNGS]
    + [("l7-mesh/v4-ingress/1024", lambda a: _v4(a, L7MESH, 0, 1024))]
    + [(f"l7-mesh/dfa/{q}x{n}x256", lambda a, q=q, n=n: _dfa(a, q, n, 256))
       for q in L7_STATES for n in L7_LANES]
    + [(f"l7-mesh/dfa-fused/{q}x{n}x256", lambda a, q=q, n=n: _dfa_fused(a, q, n, 256))
       for q in L7_STATES_FUSED for n in L7_LANES]
    + [(f"l7-mesh/dfa/118x4096x{m}", lambda a, m=m: _dfa(a, 118, 4096, m))
       for m in L7_LENS[:-1]]
)


@pytest.mark.parametrize("name,lower", CASES, ids=[c[0] for c in CASES])
def test_compiles_for_v5e(chip, name, lower):
    compiled = lower(_a(chip)).compile()
    mem = compiled.memory_analysis()
    if mem is not None:
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
