"""Device time per layer of the model, and the program's own host spans.

The program names its layers on the device with ``jax.named_scope``
(``repro.obs`` lists them): each instruction of a compiled program carries
its scope in the ``op_name`` of its metadata, forward and backward alike
(``jvp(nmp0)/edge_agg/...``, ``transpose(jvp(nmp0))/edge_agg/...``). The
profiler's op events carry the instruction's name, which is what
``bench/trace.py`` keys ``rec.trace["ops"]`` by. So the compiled text of the
program that ran (``repro.obs.hlo``), read after the window, maps each op of
the window to a layer.

``rec.trace["ops"]`` is keyed by instruction name across all the programs
that ran, and two programs can both have a ``fusion.18``. A name goes by the
table of the model's program (``grad_step`` or ``rollout_predict``) first,
then by the other programs' tables (``update``); how many names of the
window collide, and the share of op time no table maps to a layer, are
printed on standard error.

Every reader returns ``None`` where the run has no trace, is of another
kind, or the program registered nothing (a program without ``repro.obs``).
"""
from __future__ import annotations

import re
import statistics
import sys

MODEL = {"train": "grad_step", "infer": "rollout_predict"}

#: layers, by the components of an op_name once transforms are taken off
SCOPES = ("enc", "dec", "loss", "adamw", "grad_sync")
NMP = re.compile(r"nmp\d+")
NMP_PARTS = ("edge_agg", "halo", "node")
LEVEL = re.compile(r"l\d+")

EDGE_AGG = re.compile(r"nmp\d+/edge_agg")
NODE = re.compile(r"nmp\d+/node")
ENCDEC_TRAIN = re.compile(r"enc|dec|loss")
ENCDEC_INFER = re.compile(r"enc|dec")

_TRANSFORM = re.compile(r"[\w.\-]+\(")
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
TOP = 5

_layer_ms: dict[int, tuple] = {}


def layer_of(op_name: str) -> str | None:
    """The layer of an instruction's ``op_name``: its first ``enc``,
    ``dec``, ``loss``, ``adamw``, ``grad_sync``, ``nmp{i}/edge_agg|halo|node``
    or ``vcycle/l{k}`` component, under whatever transforms. Where several
    names are joined with ``;``, the first with a layer gives it."""
    for part in op_name.split(";"):
        comps = _TRANSFORM.sub("", part).replace(")", "").split("/")
        for c, nxt in zip(comps, comps[1:] + [""]):
            if c in SCOPES:
                return c
            if NMP.fullmatch(c) and nxt in NMP_PARTS:
                return f"{c}/{nxt}"
            if c == "vcycle" and LEVEL.fullmatch(nxt):
                return f"vcycle/{nxt}"
    return None


def table(hlo_text: str) -> dict[str, str | None]:
    """Instruction name -> layer (or None) for every instruction of an HLO
    module's text; names are unique within a module. An instruction with no
    ``op_name`` was made by the compiler (a reduction split in two, a layout
    copy): it goes with its first operand that has a layer, else with its
    first user that has one."""
    out: dict[str, str | None] = {}
    users: dict[str, list[str]] = {}
    made = []
    for line in hlo_text.splitlines():
        m = _NAME.match(line)
        if not m:
            continue
        name = m.group(1)
        operands = _OPERAND.findall(line, m.end())
        for o in operands:
            users.setdefault(o, []).append(name)
        op = _OP_NAME.search(line)
        if op:
            out[name] = layer_of(op.group(1))
        else:
            out[name] = next((out[o] for o in operands if out.get(o)), None)
            if out[name] is None:
                made.append(name)
    for name in reversed(made):     # a user is resolved before its operands
        out[name] = next((out[u] for u in users.get(name, ()) if out.get(u)), None)
    return out


def attribute(ops: dict, tables: list[dict]) -> tuple[dict, dict, int]:
    """Seconds of op time per layer. A name goes by the first table that
    holds it. Returns the layers' seconds, the seconds of each name no table
    maps to a layer, and how many names of ``ops`` the first table shares
    with a later one."""
    later = set().union(*tables[1:])
    by: dict[str, float] = {}
    unmapped: dict[str, float] = {}
    collide = 0
    for name, s in ops.items():
        layer = next((t[name] for t in tables if name in t), None)
        collide += name in tables[0] and name in later
        if layer is None:
            unmapped[name] = s
        else:
            by[layer] = by.get(layer, 0.0) + s
    return by, unmapped, collide


def _obs():
    try:
        from repro import obs
    except ImportError:             # a program without tracing
        return None
    return obs


def _attribute_run(rec) -> dict | None:
    obs = _obs()
    model = MODEL.get(rec.kind)
    if obs is None or model not in obs.programs():
        return None
    try:
        names = [model] + [n for n in obs.programs() if n != model]
        tables = [table(obs.hlo(n)) for n in names]
    except Exception as e:          # noqa: BLE001 - reported, not raised
        print(f"scopes: the compiled text of {model} is not available ({e!r})",
              file=sys.stderr)
        return None
    ops = rec.trace["ops"]
    by, unmapped, collide = attribute(ops, tables)
    total = sum(ops.values()) or 1.0
    worst = sorted(unmapped.items(), key=lambda kv: -kv[1])[:TOP]
    print(f"scopes: {len(ops)} op names in the window, {collide} in {model} and in "
          f"{names[1:]} both; unmapped {100.0 * sum(unmapped.values()) / total:.3f}% of "
          f"{total:.6f} s of op time (largest: {worst}); per layer, s: "
          f"{ {k: round(v, 6) for k, v in sorted(by.items())} }", file=sys.stderr)
    return {k: 1e3 * v / rec.units for k, v in by.items()}


def layer_ms(rec) -> dict | None:
    """Device ms per step or request by layer, over the traced window; worked
    out once per trace."""
    if rec.trace is None or not rec.units:
        return None
    key = id(rec.trace)
    if key not in _layer_ms:
        # the trace is kept with its result, so that its id is not reused
        _layer_ms[key] = (rec.trace, _attribute_run(rec))
    return _layer_ms[key][1]


def device_ms(rec, kind: str, layers: re.Pattern) -> float | None:
    """Device ms per step or request under the layers ``layers`` matches."""
    if rec.kind != kind:
        return None
    by = layer_ms(rec)
    if by is None:
        return None
    return sum(v for k, v in by.items() if layers.fullmatch(k))


def span_ms(rec, kind: str, name: str) -> float | None:
    """Median ms of the program's span ``name`` inside the traced window."""
    if rec.kind != kind or rec.trace is None:
        return None
    obs = _obs()
    if obs is None:
        return None
    d = [b - a for n, a, b, _ in obs.spans(*rec.window) if n == name]
    return 1e3 * statistics.median(d) if d else None
