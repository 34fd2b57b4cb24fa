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

Op time is summed over the chips and divided by their number, so a layer's
device ms on several chips is one chip's, on average.

The same compiled text gives each instruction's opcode, from which
``exposed_collective_ms`` picks the collectives of the window.

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
HALO = re.compile(r"nmp\d+/halo")
NODE = re.compile(r"nmp\d+/node")
GRAD_SYNC = re.compile(r"grad_sync")
ENCDEC_TRAIN = re.compile(r"enc|dec|loss")
ENCDEC_INFER = re.compile(r"enc|dec")

_TRANSFORM = re.compile(r"[\w.\-]+\(")
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s")
TOP = 5

#: collective opcodes; their async forms end in ``-start`` / ``-done``
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")

_layer_ms: dict[int, tuple] = {}
_texts: dict[int, tuple] = {}


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


def is_collective(opcode: str) -> bool:
    for suffix in ("-start", "-done"):
        opcode = opcode.removesuffix(suffix)
    return opcode in COLLECTIVES


def collectives(hlo_text: str) -> dict[str, bool]:
    """Instruction name -> whether it is a collective, for every instruction
    of an HLO module's text: a collective opcode (or its ``-start`` /
    ``-done``), or a fusion or async op whose called computation holds one."""
    out: dict[str, bool] = {}
    holds: dict[str, bool] = {}
    calls: dict[str, list[str]] = {}
    comp = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c and line.rstrip().endswith("{"):
            comp = c.group(1)
            holds.setdefault(comp, False)
            continue
        m = _NAME.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OPCODE.search(line, m.end())
        out[name] = bool(op) and is_collective(op.group(1))
        calls[name] = _CALLS.findall(line)
        if comp is not None:
            holds[comp] = holds[comp] or out[name]
    for name, called in calls.items():
        out[name] = out[name] or any(holds.get(c, False) for c in called)
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


def _programs(rec) -> tuple[list[str], list[str]] | None:
    """The names of the programs that ran, the model's first, and their
    compiled text; read once per trace."""
    key = id(rec.trace)
    if key not in _texts:
        _texts[key] = (rec.trace, _read_programs(rec))
    return _texts[key][1]


def _read_programs(rec):
    obs = _obs()
    model = MODEL.get(rec.kind)
    if obs is None or model not in obs.programs():
        return None
    try:
        names = [model] + [n for n in obs.programs() if n != model]
        return names, [obs.hlo(n) for n in names]
    except Exception as e:          # noqa: BLE001 - reported, not raised
        print(f"scopes: the compiled text of {model} is not available ({e!r})",
              file=sys.stderr)
        return None


def _attribute_run(rec) -> dict | None:
    progs = _programs(rec)
    if progs is None:
        return None
    names, tables = progs[0], [table(t) for t in progs[1]]
    model = names[0]
    ops = rec.trace["ops"]
    by, unmapped, collide = attribute(ops, tables)
    total = sum(ops.values()) or 1.0
    worst = sorted(unmapped.items(), key=lambda kv: -kv[1])[:TOP]
    print(f"scopes: {len(ops)} op names in the window, {collide} in {model} and in "
          f"{names[1:]} both; unmapped {100.0 * sum(unmapped.values()) / total:.3f}% of "
          f"{total:.6f} s of op time (largest: {worst}); per layer, s: "
          f"{ {k: round(v, 6) for k, v in sorted(by.items())} }", file=sys.stderr)
    return {k: 1e3 * v / rec.units / rec.trace["n_devices"] for k, v in by.items()}


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


def exposed_collective_ms(rec, kind: str) -> float | None:
    """Device ms per step or request in which a collective runs on a chip
    and no other op runs there, averaged over the chips; None where no
    collective ran in the window."""
    if rec.kind != kind or rec.trace is None or not rec.units:
        return None
    progs = _programs(rec)
    if progs is None:
        return None
    tables = [collectives(t) for t in progs[1]]
    picked = {}

    def collective(name):
        if name not in picked:
            picked[name] = next((t[name] for t in tables if name in t), False)
        return picked[name]
    from bench import trace
    s = trace.exposed_s(rec.trace, collective)
    coll = {n: v for n, v in rec.trace["ops"].items() if collective(n)}
    print(f"collectives: {len(coll)} op names, {sum(coll.values()):.6f} s of op time "
          f"over the chips (largest: {sorted(coll.items(), key=lambda kv: -kv[1])[:TOP]}); "
          f"exposed {s} s a chip", file=sys.stderr)
    return None if s is None else 1e3 * s / rec.units


def span_ms(rec, kind: str, name: str) -> float | None:
    """Median ms of the program's span ``name`` inside the traced window."""
    if rec.kind != kind or rec.trace is None:
        return None
    obs = _obs()
    if obs is None:
        return None
    d = [b - a for n, a, b, _ in obs.spans(*rec.window) if n == name]
    return 1e3 * statistics.median(d) if d else None
