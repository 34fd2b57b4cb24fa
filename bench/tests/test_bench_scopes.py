"""Per-layer device time from the compiled program's scopes, and the
program's host spans, as the new per-layer readers read them."""
from types import SimpleNamespace

import pytest

from bench import harness, scopes

#: a grad step: one instruction per layer, a backward one, a ``;``-joined
#: op_name, compiler-made instructions with no op_name, and a fused
#: computation whose inner instructions never appear in a trace
GRAD_STEP = """\
HloModule jit_call, entry_computation_layout={()->f32[]}

%fused_computation.3 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.9 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(call)/jvp(nmp0)/edge_agg/mul"}
}

ENTRY %main.1 (x.1: f32[8]) -> f32[] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(call)/jvp(enc)/dot_general" stack_frame_id=3}
  %fusion.2 = f32[8]{0:T(256)} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(call)/jvp(nmp0)/edge_agg/mul"}
  %scatter.3 = f32[8]{0} scatter(%fusion.2), metadata={op_name="jit(call)/transpose(jvp(nmp0))/edge_agg/scatter-add"}
  %fusion.4 = f32[8]{0} fusion(%scatter.3), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(call)/jvp(nmp1)/node/add"}
  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(call)/jvp(nmp1)/halo/x;jit(call)/jvp(dec)/y"}
  %copy.6 = f32[8]{0} copy(%fusion.5)
  %broadcast.7 = f32[8]{0} broadcast(%x.1)
  %fusion.8 = f32[8]{0} fusion(%broadcast.7), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(call)/shard_map/jvp(main)/loop;jit(call)/jvp(dec)/mul"}
  %reduce.9 = f32[] reduce(%fusion.8), metadata={op_name="jit(call)/jvp(loss)/reduce_sum"}
  %fusion.10 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(call)/jvp(vcycle/l1)/edge_agg/mul"}
  %bitcast.12 = f32[8]{0} bitcast(%x.1)
  %copy.13 = f32[8]{0} copy(%bitcast.12)
  %fusion.14 = f32[8]{0} fusion(%copy.13), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(call)/jvp(nmp1)/node/mul"}
  ROOT %tuple.11 = (f32[], f32[8]{0}) tuple(%reduce.9, %copy.6), metadata={op_name="jit(call)/grad_sync"}
}
"""

#: the optimizer step: ``fusion.1`` collides with the grad step's
UPDATE = """\
ENTRY %main.2 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%f, metadata={op_name="jit(update)/adamw/mul"}
  ROOT %fusion.20 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%f, metadata={op_name="jit(update)/adamw/sqrt"}
}
"""

OPS = {"fusion.1": 0.010, "fusion.2": 0.100, "scatter.3": 0.060, "fusion.4": 0.020,
       "fusion.5": 0.004, "copy.6": 0.002, "fusion.8": 0.006, "reduce.9": 0.001,
       "fusion.10": 0.003, "fusion.20": 0.008, "transfer.99": 0.002}

NEW_READERS = {"train": ["edge_agg_ms.train", "node_ms.train", "encdec_ms.train",
                         "host_batch_ms.train"],
               "infer": ["edge_agg_ms.infer", "node_ms.infer", "encdec_ms.infer",
                         "engine_queue_ms.infer", "engine_gather_ms.infer",
                         "engine_predict_ms.infer", "engine_scatter_ms.infer"]}


class FakeObs:
    def __init__(self, texts: dict, spans=()):
        self.texts, self._spans = texts, list(spans)

    def programs(self):
        return sorted(self.texts)

    def hlo(self, name):
        return self.texts[name]

    def spans(self, t0, t1):
        return [s for s in self._spans if t0 <= s[1] and s[2] <= t1]


def record(kind="train", trace=True, units=2):
    return SimpleNamespace(kind=kind, units=units, window=(10.0, 20.0),
                           trace={"ops": dict(OPS)} if trace else None)


def test_layer_of_takes_off_transforms_and_reads_the_first_layer():
    assert scopes.layer_of("jit(call)/transpose(jvp(nmp3))/edge_agg/mul") == "nmp3/edge_agg"
    assert scopes.layer_of("jit(call)/jvp(vcycle/l2)/node/add") == "vcycle/l2"
    assert scopes.layer_of("jit(update)/adamw/sqrt") == "adamw"
    assert scopes.layer_of("jit(call)/jvp(nmp0)/concatenate") is None
    assert scopes.layer_of("jit(loss_local)/mul") is None
    assert scopes.layer_of("jit(call)/a;jit(call)/jvp(dec)/b;jit(call)/jvp(enc)/c") == "dec"


def test_table_maps_every_instruction_and_inherits_for_compiler_made_ones():
    t = scopes.table(GRAD_STEP)
    assert t["fusion.1"] == "enc" and t["fusion.2"] == "nmp0/edge_agg"
    assert t["scatter.3"] == "nmp0/edge_agg" and t["fusion.4"] == "nmp1/node"
    assert t["fusion.5"] == "nmp1/halo"          # the first of the joined names
    assert t["fusion.8"] == "dec"                # the first with a layer
    assert t["copy.6"] == "nmp1/halo"            # no op_name: its operand's layer
    assert t["broadcast.7"] == "dec"             # no layered operand: its user's
    assert t["bitcast.12"] == t["copy.13"] == "nmp1/node"   # through a chain
    assert t["reduce.9"] == "loss" and t["fusion.10"] == "vcycle/l1"
    assert t["x.1"] is None and t["multiply.9"] == "nmp0/edge_agg"


def test_device_ms_per_layer_on_a_synthetic_trace(monkeypatch, capsys):
    monkeypatch.setattr(scopes, "_obs", lambda: FakeObs({"grad_step": GRAD_STEP,
                                                         "update": UPDATE}))
    rec = record()
    by = scopes.layer_ms(rec)
    # ms per step: seconds * 1e3 / 2 steps; fusion.1 goes by the grad step's table
    assert by == pytest.approx({"enc": 5.0, "nmp0/edge_agg": 80.0, "nmp1/node": 10.0,
                                "nmp1/halo": 3.0, "dec": 3.0, "loss": 0.5,
                                "vcycle/l1": 1.5, "adamw": 4.0})
    err = capsys.readouterr().err
    assert "1 in grad_step and in ['update'] both" in err
    assert f"unmapped {100 * 0.002 / sum(OPS.values()):.3f}%" in err
    assert "transfer.99" in err
    assert scopes.device_ms(rec, "train", scopes.EDGE_AGG) == pytest.approx(80.0)
    assert scopes.device_ms(rec, "train", scopes.NODE) == pytest.approx(10.0)
    assert scopes.device_ms(rec, "train", scopes.ENCDEC_TRAIN) == pytest.approx(8.5)
    assert scopes.device_ms(rec, "train", scopes.ENCDEC_INFER) == pytest.approx(8.0)
    assert scopes.layer_ms(rec) is by               # worked out once per trace
    assert capsys.readouterr().err == ""


def test_span_medians_inside_the_window(monkeypatch):
    spans = [("train/batch", 11.0, 11.030, 1), ("train/batch", 12.0, 12.020, 2),
             ("train/batch", 13.0, 13.040, 3), ("train/batch", 9.0, 9.5, 0),
             ("engine/gather", 14.0, 14.010, 7)]
    monkeypatch.setattr(scopes, "_obs", lambda: FakeObs({"grad_step": GRAD_STEP}, spans))
    assert scopes.span_ms(record(), "train", "train/batch") == pytest.approx(30.0)
    assert scopes.span_ms(record(), "train", "engine/queue_wait") is None


@pytest.mark.parametrize("kind", sorted(NEW_READERS))
def test_new_readers_give_none_without_a_trace_or_of_the_other_kind(monkeypatch, kind):
    other = "infer" if kind == "train" else "train"
    spans = [(n, 11.0, 11.001, 0) for n in ("train/batch", "engine/queue_wait",
                                            "engine/gather", "engine/predict",
                                            "engine/scatter")]
    monkeypatch.setattr(scopes, "_obs", lambda: FakeObs(
        {"grad_step": GRAD_STEP, "rollout_predict": GRAD_STEP}, spans))
    for name in NEW_READERS[kind]:
        read = harness.metric_reader(name).read
        assert read(record(kind)) is not None, name
        assert read(record(kind, trace=False)) is None, name
        assert read(record(other)) is None, name


def test_new_readers_give_none_where_the_program_has_no_tracing(monkeypatch):
    monkeypatch.setattr(scopes, "_obs", lambda: None)
    for kind, names in NEW_READERS.items():
        for name in names:
            assert harness.metric_reader(name).read(record(kind)) is None, name
