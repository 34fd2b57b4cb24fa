"""Host seconds of the graph build in set-up: mesh generation, partition,
and ``ShardedGraph.build`` with its placement (the engine's
``register_mesh`` for inference), from the benchmark's own spans."""


def read(rec):
    return rec.spans.total("mesh_gen", "partition", "graph_build") or None
