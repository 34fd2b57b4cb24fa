"""Gather-only segment sum through per-node slot tables.

``slot_segment_sum`` / ``slot_gather`` (``repro.graph.segment``) must equal
``jax.ops.segment_sum`` / ``jnp.take`` in value and in gradient, with and
without a leading batch axis, on bounded-degree graphs with isolated nodes,
padded edges and masked edges. A graph with a hub gets no tables, and the
xla NMP layer then keeps its scatter path.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import GNNConfig, box_mesh, init_gnn, partition_mesh
from repro.core.consistent_mp import edge_update_aggregate
from repro.core.graph_state import NMPPlan, ShardedGraph
from repro.core.partition import SLOT_FILL, partition_graph
from repro.graph import segment

B = 3
BATCHING = ("none", "leading", "vmap")
# rows of 5 and 8 are gathered as they are; 32 and 64 as 128-lane rows of
# 4 and 2 edges
WIDTHS = (5, 8, 32, 64)


def _bounded_graph(R: int, seed: int):
    """A randomly relabelled circulant graph (every node linked to the next
    two around a ring, both ways), a few isolated nodes, some undirected
    edges dropped, partitioned over R ranks; then a few real edges masked."""
    rng = np.random.default_rng(seed)
    n_ring, n_iso = 120, 3
    ring = np.arange(n_ring)
    und = np.concatenate([np.stack([ring, (ring + k) % n_ring], 1)
                          for k in (1, 2)])
    und = und[rng.random(len(und)) > 0.05]
    label = rng.permutation(n_ring + n_iso)
    und = label[und]
    directed = np.concatenate([und, und[:, ::-1]])
    # ranks hold arcs of the ring (and the isolated nodes spread over them)
    node2part = np.empty(n_ring + n_iso, dtype=np.int64)
    node2part[label] = np.arange(n_ring + n_iso) * R // (n_ring + n_iso)
    pg = partition_graph(n_ring + n_iso, directed, R, node2part=node2part)
    real = np.argwhere(pg.edge_mask > 0)
    drop = real[rng.choice(len(real), size=len(real) // 10, replace=False)]
    pg.edge_mask[drop[:, 0], drop[:, 1]] = 0.0
    pg.edge_inv_mult[drop[:, 0], drop[:, 1]] = 0.0
    return pg


@pytest.fixture(scope="module", params=[1, 2], ids=["R1", "R2"])
def graph(request):
    pg = _bounded_graph(request.param, seed=request.param)
    tables = pg.slot_tables()
    assert tables is not None
    return pg, tables


def _rank(pg, tables, r):
    return dict(src=jnp.asarray(pg.edge_src[r]), dst=jnp.asarray(pg.edge_dst[r]),
                mask=jnp.asarray(pg.edge_mask[r])[:, None],
                ins=jnp.asarray(tables["in_slots"][r]),
                outs=jnp.asarray(tables["out_slots"][r]))


def _batched(fn, batching):
    """``fn`` over [E|N, F] rows, applied as the batching asks."""
    if batching == "vmap":
        return jax.vmap(fn)
    return fn


def _inputs(pg, batching, seed, width):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    lead = () if batching == "none" else (B,)
    return (jax.random.normal(k1, lead + (pg.e_pad, width)),
            jax.random.normal(k2, lead + (pg.n_pad, width)))


def test_slot_tables_invert_the_edge_lists(graph):
    pg, tables = graph
    keep = pg.edge_mask > 0
    width = tables["in_slots"].shape[-1]
    assert tables["out_slots"].shape == (pg.R, pg.n_pad, width)
    assert pg.n_pad * width <= SLOT_FILL * pg.e_pad
    for name, ids in (("in_slots", pg.edge_dst), ("out_slots", pg.edge_src)):
        t = tables[name]
        assert t.dtype == np.int32
        for r in range(pg.R):
            listed = t[r][t[r] < pg.e_pad]
            # every kept edge once, no masked or padded edge
            np.testing.assert_array_equal(np.sort(listed), np.nonzero(keep[r])[0])
            for n in range(pg.n_pad):
                row = t[r, n][t[r, n] < pg.e_pad]
                assert (ids[r][row] == n).all()
                assert (np.diff(row) > 0).all()          # edge order
                # padding only after the listed edges
                assert (t[r, n][len(row):] == pg.e_pad).all()
    assert pg.slot_tables() is tables                    # memoized


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("batching", BATCHING)
def test_slot_segment_sum_equals_segment_sum(graph, batching, width):
    pg, tables = graph
    for r in range(pg.R):
        g = _rank(pg, tables, r)
        data, _ = _inputs(pg, batching, seed=r, width=width)
        data = data * g["mask"]
        got = _batched(lambda d: segment.slot_segment_sum(d, g["dst"], g["ins"]),
                       batching)(data)
        want = (jax.vmap if batching != "none" else (lambda f: f))(
            lambda d: jax.ops.segment_sum(d, g["dst"], num_segments=pg.n_pad))(data)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n_edges", [6, 8])
def test_slot_segment_sum_packs_a_partial_lane_row(n_edges):
    """6 edges of 32 lanes fill one and a half 128-lane rows; the padding
    slot (id E) reads zero either way."""
    ids = jnp.asarray([2, 0, 2, 1, 0, 2, 1, 1][:n_edges], jnp.int32)
    slots = np.full((3, 3), n_edges, np.int32)
    for n in range(3):
        own = np.nonzero(np.asarray(ids) == n)[0]
        slots[n, :len(own)] = own
    data = jax.random.normal(jax.random.PRNGKey(0), (n_edges, 32))
    np.testing.assert_allclose(
        segment.slot_segment_sum(data, ids, jnp.asarray(slots)),
        jax.ops.segment_sum(data, ids, num_segments=3), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("batching", BATCHING)
def test_slot_gather_equals_take(graph, batching):
    pg, tables = graph
    for r in range(pg.R):
        g = _rank(pg, tables, r)
        _, x = _inputs(pg, batching, seed=r, width=5)
        for idx, slots in ((g["src"], g["outs"]), (g["dst"], g["ins"])):
            got = _batched(lambda v: segment.slot_gather(v, idx, slots), batching)(x)
            np.testing.assert_array_equal(got, jnp.take(x, idx, axis=-2))


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("batching", BATCHING)
def test_slot_vjps_equal_autodiff(graph, batching, width):
    """Through a masked edge function, as the NMP layer uses them: the
    custom VJPs (gathers) equal autodiff of segment_sum and take
    (scatter-adds)."""
    pg, tables = graph
    for r in range(pg.R):
        g = _rank(pg, tables, r)
        data, x = _inputs(pg, batching, seed=10 + r, width=width)

        def loss(data, x, slot):
            if slot:
                s = lambda d: segment.slot_segment_sum(d, g["dst"], g["ins"])  # noqa: E731
                xi = segment.slot_gather(x, g["src"], g["outs"])
                xj = segment.slot_gather(x, g["dst"], g["ins"])
            else:
                s = lambda d: segment.segment_sum(d, g["dst"], pg.n_pad)  # noqa: E731
                xi, xj = segment.gather(x, g["src"]), segment.gather(x, g["dst"])
            edge = jnp.tanh(xi * xj + data) * g["mask"]
            agg = (s if batching == "none" else jax.vmap(s))(edge)
            return (agg ** 2).sum() + (jnp.sin(agg) * x).sum()

        want = jax.grad(loss, argnums=(0, 1))(data, x, False)
        if batching == "vmap":
            got = jax.vmap(jax.grad(lambda d, v: loss(d[None], v[None], True),
                                    argnums=(0, 1)))(data, x)
        else:
            got = jax.grad(loss, argnums=(0, 1))(data, x, True)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def _layer(pg, batched: bool, seed: int):
    cfg = GNNConfig(hidden=8, n_mp_layers=1, mlp_hidden_layers=2)
    lp = init_gnn(jax.random.PRNGKey(seed), cfg)["mp"][0]
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    lead = (B,) if batched else ()
    x = jax.random.normal(k1, lead + (pg.n_pad, cfg.hidden))
    e = jax.random.normal(k2, lead + (pg.e_pad, cfg.hidden))

    def loss(p, x, e, g):
        e_new, agg = edge_update_aggregate(p, x, e, g, NMPPlan())
        return (e_new ** 2).sum() + (jnp.cos(agg) * agg).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2)), (lp, x, e)


@pytest.mark.parametrize("batched", [False, True], ids=["unbatched", "batched"])
def test_xla_layer_with_tables_equals_scatter_path(batched):
    """Eq. 4a-b on the box mesh's graph: the same value and gradients with
    the slot tables as without them (only the summation order may move)."""
    mesh = box_mesh((4, 2, 2), p=2)
    pg = partition_mesh(mesh, (1, 1, 1))
    g_slots = ShardedGraph.build(pg, mesh.coords)
    assert "in_slots" in g_slots
    g_scatter = ShardedGraph.from_arrays(
        {k: v for k, v in g_slots.items() if k not in ("in_slots", "out_slots")})
    fn, args = _layer(pg, batched, seed=3)
    (v1, g1), (v0, g0) = [jax.jit(fn)(*args, g.rank(0))
                          for g in (g_slots, g_scatter)]
    np.testing.assert_allclose(v1, v0, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_star_graph_gets_no_tables_and_keeps_the_scatter_path():
    n = 40
    leaves = np.arange(1, n)
    und = np.stack([np.zeros_like(leaves), leaves], 1)
    pg = partition_graph(n, np.concatenate([und, und[:, ::-1]]), 1)
    assert pg.n_pad * (n - 1) > SLOT_FILL * pg.e_pad
    assert pg.slot_tables() is None
    coords = np.random.default_rng(0).random((n, 3))
    graph = ShardedGraph.build(pg, coords)
    assert "in_slots" not in graph and "out_slots" not in graph
    fn, args = _layer(pg, batched=False, seed=5)
    txt = jax.jit(fn).lower(*args, graph.rank(0)).as_text()
    assert "scatter" in txt and "slot_sum" not in txt
    value, grads = jax.jit(fn)(*args, graph.rank(0))
    assert np.isfinite(value)
    assert all(np.isfinite(g).all() for g in jax.tree.leaves(grads))
