"""Compile rehearsal for a TPU v5e: the main path's Pallas kernels must pass
the chip's compiler at H=32 and at the chip smoke's consistency size
(``box_mesh((4, 4, 4), p=4)``, 4,913 nodes). Nothing runs: the kernels are
compiled for a described ``v5e:2x2`` topology, which catches the tiling,
SMEM and VMEM refusals that interpret mode cannot see.

The topology is described inside a module-scoped fixture (never while a
module is imported), so every pytest-xdist worker collects the same tests
and only the worker given this file loads the TPU compiler.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (
    GNNConfig, box_mesh, init_gnn, mesh_graph_edges, partition_mesh)
from repro.core.consistent_mp import nmp_layer
from repro.core.graph_state import NMPPlan, ShardedGraph
from repro.core.mesh_gen import undirected_to_directed
from repro.kernels.halo_pack.kernel import pack_pallas, unpack_add_pallas
from repro.kernels.segment_agg.kernel import (
    nmp_edge_mlp_agg_bwd, nmp_edge_mlp_agg_fwd)
from repro.kernels.segment_agg.ops import (
    compact_gather_layout, fused_nmp_edge_agg, pick_block_sizes)

H = 32                  # GNNConfig.large() hidden width
N_HIDDEN = 5            # its MLP hidden layers
ELEMENTS, ORDER = (4, 4, 4), 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def layout():
    """The smoke mesh's compact edge layout at the TPU block sizes."""
    mesh = box_mesh(ELEMENTS, p=ORDER)
    edges = undirected_to_directed(mesh_graph_edges(mesh))
    _, block_e = pick_block_sizes(H, backend="tpu")
    lay = compact_gather_layout(edges[:, 0], edges[:, 1], mesh.n_nodes,
                                block_e)
    n_round = -(-mesh.n_nodes // 8) * 8
    return n_round, lay


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _nmp_args(n_round, lay, sharding):
    T, BE = lay["perm"].shape
    S = lambda shape, dt=jnp.float32: _spec(shape, sharding, dt)  # noqa: E731
    return (S((n_round, H)), S((T, BE, H)), S((T, BE), jnp.int32),
            S((T, BE), jnp.int32), S((T, BE)), S((T, BE)), S((3 * H, H)),
            S((1, H)), S((N_HIDDEN, H, H)), S((N_HIDDEN, H)), S((1, H)),
            S((1, H)))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_fused_nmp_forward_compiles(one_chip, layout):
    n_round, lay = layout
    _compile(lambda *a: nmp_edge_mlp_agg_fwd(
        *a, block_e=lay["block_e"], n_hidden=N_HIDDEN, has_ln=True),
        *_nmp_args(n_round, lay, one_chip))


def test_fused_nmp_backward_compiles(one_chip, layout):
    n_round, lay = layout
    T, BE = lay["perm"].shape
    _compile(lambda *a: nmp_edge_mlp_agg_bwd(
        *a, block_e=BE, n_hidden=N_HIDDEN, has_ln=True),
        *_nmp_args(n_round, lay, one_chip),
        _spec((T, BE, H), one_chip), _spec((n_round, H), one_chip))


def test_fused_nmp_op_value_and_grad_compiles(one_chip, layout):
    """The differentiable op as the fused backend calls it: both kernels
    plus the layout gathers around them, in one program."""
    n_round, lay = layout
    params = jax.eval_shape(lambda: init_gnn(
        jax.random.PRNGKey(0), GNNConfig.large()))["mp"][0]["edge"]
    e_pad = int(lay["n_edges"])
    rep = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _spec(a.shape, one_chip, a.dtype), t)

    def loss(p, x, e, perm, src, dst, mask, inv):
        e_new, agg = fused_nmp_edge_agg(x, e, p, perm, src, dst, mask, inv)
        return (e_new ** 2).sum() + (agg ** 2).sum()

    T, BE = lay["perm"].shape
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), rep(params),
             _spec((n_round, H), one_chip), _spec((e_pad, H), one_chip),
             *(_spec((T, BE), one_chip, jnp.int32) for _ in range(3)),
             _spec((e_pad,), one_chip), _spec((e_pad,), one_chip))


def test_xla_edge_agg_compiles_without_scatter_or_sort(one_chip):
    """The xla backend's Eq. 4a-b, forward and backward, on the smoke mesh:
    a bounded-degree graph carries slot tables, so the compiled layer sums
    and transposes its gathers by gathers, with no scatter and no sort
    under ``edge_agg``."""
    import re
    mesh = box_mesh(ELEMENTS, p=ORDER)
    graph = ShardedGraph.build(partition_mesh(mesh, (1, 1, 1)), mesh.coords)
    assert "in_slots" in graph
    local = jax.tree.map(lambda a: _spec(a.shape[1:], one_chip, a.dtype), graph)
    params = jax.eval_shape(lambda: init_gnn(
        jax.random.PRNGKey(0), GNNConfig.large()))["mp"][0]
    n_pad, e_pad = graph["node_mask"].shape[-1], graph["edge_mask"].shape[-1]

    def loss(p, x, e, g):
        with jax.named_scope("nmp0"):
            x_new, e_new = nmp_layer(p, x, e, g, NMPPlan())
        return (x_new ** 2).sum() + (e_new ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        jax.tree.map(lambda a: _spec(a.shape, one_chip, a.dtype), params),
        _spec((n_pad, H), one_chip), _spec((e_pad, H), one_chip),
        local).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert any("nmp0)/edge_agg/slot_sum" in o for o in op_names)
    under = [line for line in text.splitlines()
             if re.search(r"\b(scatter|sort)\(", line.split("metadata=")[0])
             and "/edge_agg/" in line]
    assert under == []


@pytest.mark.parametrize("wire_rows", [128, 2048])
def test_halo_pack_and_unpack_compile(one_chip, layout, wire_rows):
    n_round, _ = layout
    block_b = 128                        # the TPU row of pick_block_b
    T = wire_rows // block_b
    idx = _spec((T, block_b), one_chip, jnp.int32)
    mask = _spec((T, block_b), one_chip)
    _compile(pack_pallas, _spec((n_round, H), one_chip), idx, mask)
    _compile(unpack_add_pallas, _spec((n_round, H), one_chip),
             _spec((T, block_b, H), one_chip), idx, mask)


def test_sizes_past_smem_or_vmem_are_refused_at_setup():
    """Past the SMEM that holds the index lists the op names the limit
    before it traces a kernel; it never falls back to another path."""
    T, BE = 300, 512                     # 2 x 1.2 MB of index lists
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in
            _nmp_args(4096, {"perm": np.zeros((T, BE))}, None)]
    with pytest.raises(ValueError, match="SMEM"):
        jax.eval_shape(lambda *a: nmp_edge_mlp_agg_fwd(
            *a, block_e=BE, n_hidden=N_HIDDEN, has_ln=True), *args)
    with pytest.raises(ValueError, match="VMEM"):
        jax.eval_shape(unpack_add_pallas,
                       jax.ShapeDtypeStruct((200_000, H), jnp.float32),
                       jax.ShapeDtypeStruct((1, 128, H), jnp.float32),
                       jax.ShapeDtypeStruct((1, 128), jnp.int32),
                       jax.ShapeDtypeStruct((1, 128), jnp.float32))
