"""The main-path device programs compile for a TPU v5e.

Compiles each program the served verdict path runs, for one chip of a
described ``v5e:2x2`` topology (the sharded verdict program for all
four, as ``chip_smoke.py --four-chips`` places it), at the widths
chip_smoke.py runs
(BASELINE headline: 10k rules, 2,048 identities, 64 endpoints, 50k
prefilter prefixes, flow batches at the top ladder rung). Nothing runs:
a compile that passes says the chip's compiler accepts the program and
that it fits, not that it is right or fast.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the driver
imports this file in every test worker.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Table widths at the smoke's size, as chip_smoke.py's `phase=tables`
# line reported them on a TPU v5 lite (PR 21).
N_ROWS = 2304          # identity rows (2,048 identities + reserved, 256-bucketed)
SEL_WORDS = 20         # sel_match words: 640 selectors / 32
S = SEL_WORDS * 32
POLICY_ID_WORDS = 53   # DevicePolicy.id_bits words
K1 = 4096              # L4 (selector, port) combos, ingress
GROUPS = 1024          # rule groups, ingress
PORTS = 16
K7 = 8
PM_COLS = 288          # ingress policymap columns over 64 endpoints
PF_SUB = 43831         # prefilter trie sub-tables for 50k prefixes
IP_SUB = 3             # identity trie 16-bit sub-tables
SEGMENTS = 512         # materialize sweep segments per dispatch (bucketed)
BATCH = 8192           # top rung of the flow bucket ladder
ENDPOINTS = 64
L7_LANES = 16384       # top L7 lane rung
L7_LEN = 128           # top L7 length rung
L7_STATES = 127        # the stride-2 table exists only up to 2^23 / 257^2 states
CT_BITS = 20           # device conntrack slots (make_state default)
V6_NODES = 8192        # v6 stride-8 trie nodes (assumed: ~2k /128 peers)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """The described chips, with the persistent compile cache off: an
    entry written here cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _policymap(a, rows=None):
    from cilium_tpu.ops.lookup import PolicymapTables

    return PolicymapTables(
        col_ep=a((PM_COLS,), jnp.int32), col_port=a((PM_COLS,), jnp.int32),
        col_proto=a((PM_COLS,), jnp.int32), col_is_l3=a((PM_COLS,), jnp.bool_),
        id_bits=(rows or a)((N_ROWS, 2 * PM_COLS // 32), jnp.uint32),
    )


def _flows(a, n=BATCH):
    return (a((n,), jnp.int32), a((n,), jnp.int32), a((n,), jnp.int32))


def _one_chip(topo):
    return _sds(SingleDeviceSharding(topo.devices[0]))


def _lookup_batch(topo):
    from cilium_tpu.ops.lookup import lookup_batch

    a = _one_chip(topo)
    ep, rows, dport = _flows(a)
    return lookup_batch.lower(_policymap(a), ep, rows, dport, a((BATCH,), jnp.int32))


def _wide_tables(a, rows=None):
    from cilium_tpu.datapath.pipeline import WideDatapathTables

    i32 = jnp.int32
    return WideDatapathTables(
        pf_root_info=a((65536,), i32), pf_root_child=a((65536,), i32),
        pf_sub_child=a((PF_SUB, 256), i32), pf_sub_info=a((PF_SUB, 256), i32),
        ip_root_info=a((65536,), i32), ip_root_child=a((65536,), i32),
        ip_sub_child=a((1, 65536), i32), ip_sub_info=a((IP_SUB, 65536), i32),
        merged_root_info=a((1,), i32), merged_root_child=a((1,), i32),
        merged_sub_child=a((1, 1), i32), merged_sub_info=a((1, 1), i32),
        world_row=a((), i32), policymap=_policymap(a, rows),
    )


def _process_flows_wide(topo):
    from cilium_tpu.datapath.pipeline import process_flows_wide

    a = _one_chip(topo)
    ep, dport, proto = _flows(a)
    return process_flows_wide.lower(
        _wide_tables(a), a((BATCH,), jnp.uint32), ep, dport, proto,
        ep_count=ENDPOINTS, prefilter=True, row_override=None,
    )


def _process_flows_wide_2x2(topo):
    """The chip_smoke --four-chips program: flows split over the
    "flows" axis, policymap rows over "ident", the rest replicated."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cilium_tpu.datapath.pipeline import process_flows_wide

    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("flows", "ident"))
    table = _sds(NamedSharding(mesh, P()))
    flows = _sds(NamedSharding(mesh, P("flows")))
    rows = _sds(NamedSharding(mesh, P("ident", None)))
    ep, dport, proto = _flows(flows)
    return process_flows_wide.lower(
        _wide_tables(table, rows), flows((BATCH,), jnp.uint32), ep, dport, proto,
        ep_count=ENDPOINTS, prefilter=True, row_override=None, ident_gather=True,
    )


def _process_flows_wide_packed(topo):
    """The served v4 program: one packed [4, lanes] chunk in, one int32
    result out."""
    from cilium_tpu.datapath.pipeline import flow_rows, process_flows_wide_packed

    a = _one_chip(topo)
    return process_flows_wide_packed.lower(
        _wide_tables(a), a((flow_rows(4, False), BATCH), jnp.int32),
        ep_count=ENDPOINTS, prefilter=True,
    )


def _process_flows_wide_packed_2x2(topo):
    """The served v4 program on the 2x2 flows x ident plan: the chunk's
    lanes split over "flows", the result replicated."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cilium_tpu.datapath.pipeline import flow_rows, process_flows_wide_packed

    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("flows", "ident"))
    table = _sds(NamedSharding(mesh, P()))
    lanes = _sds(NamedSharding(mesh, P(None, "flows")))
    rows = _sds(NamedSharding(mesh, P("ident", None)))
    return process_flows_wide_packed.lower(
        _wide_tables(table, rows), lanes((flow_rows(4, True), BATCH), jnp.int32),
        ep_count=ENDPOINTS, prefilter=True, ident_gather=True,
        out_sharding=NamedSharding(mesh, P()),
    )


def _process_flows_packed_v6(topo):
    """The served v6 program (fused deny+identity walk): one packed
    [19, lanes] chunk in, one int32 result out."""
    from cilium_tpu.datapath.pipeline import (
        DatapathTables, flow_rows, process_flows_packed,
    )

    a = _one_chip(topo)
    i32 = jnp.int32
    trie = lambda n: (a((n, 256), i32), a((n, 256), i32), a((2,), i32))  # noqa: E731
    (pc, pi, pcm), (ic, ii, icm), (mc, mi, mcm) = trie(V6_NODES), trie(V6_NODES), trie(V6_NODES)
    t = DatapathTables(
        pf_child=pc, pf_info=pi, pf_common=pcm, ip_child=ic, ip_info=ii, ip_common=icm,
        merged_child=mc, merged_info=mi, merged_common=mcm, world_row=a((), i32),
        policymap=_policymap(a),
    )
    return process_flows_packed.lower(
        t, a((flow_rows(6, False), BATCH), i32), ep_count=ENDPOINTS, levels=16,
        prefilter=True, fused=True,
    )


def _sweep_device_matrix(topo):
    from cilium_tpu.ops.materialize import _MATRIX_NBLOCK, _sweep_device_matrix
    from cilium_tpu.ops.verdict import DevicePolicy, DeviceTables

    a = _one_chip(topo)
    i8 = jnp.int8
    tables = DeviceTables(
        deny_t=a((S, S), i8), allow_t=a((S, S), i8),
        ports=a((PORTS,), jnp.int32), protos=a((PORTS,), jnp.int32),
        s1_mat=a((S, K1), i8), p1_mat=a((PORTS, K1), i8),
        en_t=a((S, K1), i8), ee_t=a((S, K1), i8),
        gpn_mat=a((S, GROUPS), i8), gpe_mat=a((S, GROUPS), i8),
        group_no_peers=a((GROUPS,), jnp.bool_),
        s7_mat=a((S, K7), i8), p7_mat=a((PORTS, K7), i8), g7_mat=a((GROUPS, K7), i8),
    )
    policy = DevicePolicy(
        id_bits=a((N_ROWS, POLICY_ID_WORDS), jnp.uint32),
        sel_match=a((N_ROWS, SEL_WORDS), jnp.uint32),
        ingress=tables, egress=tables,
    )
    seg = a((SEGMENTS,), jnp.int32)
    return _sweep_device_matrix.lower(
        policy, seg, seg, seg, a((SEGMENTS,), jnp.bool_),
        N_ROWS, True, _MATRIX_NBLOCK,
    )


def _dfa_pair_walk(topo):
    from cilium_tpu.ops.dfa import PAIR_ALPHA, dfa_match_batch_pair

    a = _one_chip(topo)
    return dfa_match_batch_pair.lower(
        a((L7_STATES, PAIR_ALPHA * PAIR_ALPHA), jnp.int32),
        a((L7_STATES,), jnp.uint32), a((L7_STATES,), jnp.uint32),
        a((L7_LANES,), jnp.int32), a((L7_LANES, L7_LEN), jnp.uint8),
        a((L7_LANES,), jnp.int32), max_len=L7_LEN,
    )


def _dfa_packed_walk(kernel, table_width):
    def lower(topo):
        from cilium_tpu.ops import dfa

        a = _one_chip(topo)
        return getattr(dfa, kernel).lower(
            a((L7_STATES, table_width), jnp.int32),
            a((L7_STATES,), jnp.uint32), a((L7_STATES,), jnp.uint32),
            a((L7_LANES, L7_LEN + dfa.PACK_HEADER), jnp.uint8), max_len=L7_LEN,
        )
    lower.__name__ = f"_{kernel}"
    return lower


def _ct_step(topo):
    from cilium_tpu.datapath.device_ct import DeviceCTState, ct_step

    a = _one_chip(topo)
    c = 1 << CT_BITS
    u32 = jnp.uint32
    state = DeviceCTState(*(a((c,), u32) for _ in range(6)), a((c,), jnp.int32))
    w = (a((BATCH,), u32), a((BATCH,), u32))
    return ct_step.lower(state, w, w, w, a((BATCH,), jnp.int32), a((), jnp.int32),
                         a((BATCH,), jnp.bool_))


@pytest.mark.parametrize("lower", [
    _lookup_batch, _process_flows_wide, _process_flows_wide_2x2,
    _sweep_device_matrix, _dfa_pair_walk, _ct_step,
    _dfa_packed_walk("dfa_match_packed_pair", 257 * 257),
    _dfa_packed_walk("dfa_match_packed_fused", 256),
    _process_flows_wide_packed, _process_flows_wide_packed_2x2,
    _process_flows_packed_v6,
], ids=lambda f: f.__name__.lstrip("_"))
def test_compiles_for_v5e(chip, lower):
    lowered = lower(chip)
    mem = lowered.compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 10**9, f"{used} bytes per 16 GB chip"
    if lower is _ct_step:
        # argument 0 (the CT table) is donated: updated in place on device
        assert "tf.aliasing_output" in lowered.as_text()
