"""The program's own tracing (``repro.obs``): host spans in a bounded ring and
on the profiler's clock, layer scopes on every compute instruction of the
compiled step, and the engine's per-request spans, on the CPU at the
4,913-node mesh (``box_mesh((4, 4, 4), p=4)``)."""
import re
import time
from pathlib import Path

import numpy as np
import pytest
import jax
from jax.sharding import AxisType

from repro import obs
from repro.ckpt import checkpoint as ckpt
from repro.core import GNNConfig, NMPPlan, box_mesh, build_hierarchy, init_gnn, partition_mesh
from repro.core.mesh_gen import taylor_green_velocity
from repro.runtime.engine import EngineConfig, InferenceEngine
from repro.train.loop import TrainConfig, build_execution, run_fingerprint
from repro.train.optimizer import init_adamw

#: a layer scope, once the transforms (``jvp(...)``, ``transpose(...)``)
#: are taken off the op_name
LAYER = re.compile(r"(^|/)(enc|dec|loss|adamw|grad_sync|nmp\d+/(edge_agg|halo|node)"
                   r"|vcycle/l\d+)(/|$)")
COMPUTE = {"fusion", "dot", "gather", "scatter", "custom-call", "reduce"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")
_COMP = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def sem():
    return box_mesh((4, 4, 4), p=4)


def _top_level(hlo: str) -> list[tuple[str, str, str | None]]:
    """(name, opcode, op_name or None) of the instructions of the computations
    that run as such: the entry and loop bodies, not fused or applied ones."""
    inner = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", hlo))
    out, keep = [], False
    for line in hlo.splitlines():
        c = _COMP.match(line)
        if c:
            keep = bool(c.group(1)) or c.group(2) not in inner
            continue
        m = _INSTR.match(line)
        if keep and m:
            op = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), op.group(1) if op else None))
    return out


def _layer(op_name: str) -> str | None:
    m = LAYER.search(re.sub(r"[\w.\-]+\(", "", op_name).replace(")", ""))
    return m.group(2) if m else None


def test_spans_record_in_order_and_the_ring_stays_bounded():
    t0 = time.perf_counter()
    for i in range(3):
        with obs.span("test/a", i):
            pass
        with obs.span("test/b"):
            pass
    got = [(n, i) for n, _, _, i in obs.spans(t0) if n.startswith("test/")]
    assert got == [("test/a", 0), ("test/b", None), ("test/a", 1), ("test/b", None),
                   ("test/a", 2), ("test/b", None)]
    assert all(a <= b for _, a, b, _ in obs.spans(t0))
    obs.record("test/late", t0 - 10.0, t0 - 9.0, "req")
    assert ("test/late", t0 - 10.0, t0 - 9.0, "req") in obs.spans(t0 - 11.0, t0 - 8.0)
    for i in range(obs.RING_SIZE + 10):
        obs.record("test/fill", 0.0, 1.0, i)
    held = obs.spans()
    assert len(held) == obs.RING_SIZE
    assert held[0][3] == 10 and held[-1][3] == obs.RING_SIZE + 9


def test_a_span_under_the_profiler_lands_on_a_host_plane(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("test/profiled", 7):
            jax.block_until_ready(jax.numpy.ones(8) * 2)
    finally:
        jax.profiler.stop_trace()
    profile = ProfileData.from_file(str(sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]))
    names = {e.name for p in profile.planes if not p.name.startswith("/device:")
             for line in p.lines for e in line.events}
    assert obs.PREFIX + "test/profiled" in names
    assert obs.spans()[-1][0] == "test/profiled"


def _train_once(sem, cfg, plan, hierarchy=None):
    pg = hierarchy.levels[0] if hierarchy is not None else partition_mesh(sem, (1, 1, 1))
    mesh = jax.make_mesh((1, 1), ("data", "graph"), axis_types=(AxisType.Auto,) * 2)
    ex = build_execution(mesh, pg, sem, cfg, TrainConfig(halo_mode="none", plan=plan),
                         hierarchy)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    opt = init_adamw(params, ex.opt_cfg)
    loss, grads = ex.grad_for_step(params, 3)
    ex.update(params, opt, loss, grads)
    return float(loss)


@pytest.mark.parametrize("case", ["blocking", "overlap", "vcycle"])
def test_every_compute_instruction_of_the_step_carries_a_layer_scope(sem, case):
    cfg = GNNConfig(hidden=8, n_mp_layers=2, mlp_hidden_layers=2)
    hierarchy = None
    if case == "vcycle":
        cfg = GNNConfig(hidden=8, n_mp_layers=1, mlp_hidden_layers=2, n_levels=2,
                        coarse_mp_layers=1)
        hierarchy = build_hierarchy(sem, (1, 1, 1), 2)
    schedule = "overlap" if case == "overlap" else "blocking"
    t0 = time.perf_counter()
    assert np.isfinite(_train_once(sem, cfg, NMPPlan(backend="xla", schedule=schedule),
                                   hierarchy))
    assert [i for n, _, _, i in obs.spans(t0) if n == "train/batch"] == [3]
    assert {"grad_step", "update"} <= set(obs.programs())

    instrs = [(n, op, on) for n, op, on in _top_level(obs.hlo("grad_step")) if op in COMPUTE]
    # the compiler's own instructions (a reduction split in two) have no
    # op_name; every one that comes from the program names its layer
    sourced = [(n, on) for n, _, on in instrs if on is not None]
    assert len(sourced) > len(instrs) / 2
    assert [(n, on) for n, on in sourced if _layer(on) is None] == []
    layers = {_layer(on) for _, on in sourced}
    want = {"enc", "dec", "loss", "nmp0/edge_agg", "nmp0/node"}
    if case == "vcycle":
        want.add("vcycle/l1")
    assert want <= layers, layers
    assert any("transpose(" in on and _layer(on) == "nmp0/edge_agg" for _, on in sourced)
    adamw = [on for n, op, on in _top_level(obs.hlo("update")) if op in COMPUTE and on]
    assert adamw and all(_layer(on) == "adamw" for on in adamw)


def test_one_requests_engine_spans_share_its_id(sem, tmp_path):
    cfg = GNNConfig(hidden=8, n_mp_layers=2, mlp_hidden_layers=2)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    fp = run_fingerprint(sem, partition_mesh(sem, (1, 1, 1)), cfg, TrainConfig(), NMPPlan())
    ckpt.save(tmp_path / "ck", 0, {"params": params}, extra={"fingerprint": fp})
    engine = InferenceEngine(tmp_path / "ck", cfg, EngineConfig(batch_slots=1, rollout_steps=1))
    mesh_hash = engine.register_mesh(sem, rank_grid=(1, 1, 1))
    t0 = time.perf_counter()
    with engine:
        x = taylor_green_velocity(sem.coords, t=0.1).astype(np.float32)
        engine.submit(mesh_hash, x).result(timeout=300)
        engine.submit(mesh_hash, x).result(timeout=300)
    got = obs.spans(t0)
    ids = {n: [i for m, _, _, i in got if m == n] for n in
           ("engine/queue_wait", "engine/gather", "engine/predict", "engine/scatter")}
    first, second = ids["engine/gather"]
    assert first != second
    for name in ("engine/queue_wait", "engine/scatter", "engine/predict"):
        assert ids[name] == [first, second], (name, ids)
    assert "rollout_predict" in obs.programs()
    layers = {_layer(on) for _, _, on in _top_level(obs.hlo("rollout_predict")) if on}
    assert {"enc", "dec", "nmp1/edge_agg", "nmp1/node"} <= layers
