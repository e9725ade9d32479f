"""Attribute a traced window's device operations to the program's layers,
and count the HBM bytes each operation moves.

**Layers.**  The program names its layers with ``jax.named_scope`` (for
ResNet18: ``stem``, ``maxpool``, ``stage1`` … ``stage4``, ``head``, the
parts a configuration's ``groups`` list).  XLA carries the scope into each
instruction's ``op_name`` metadata, but a profiler trace names an
operation only by its HLO instruction (``%fusion.11 = f32[...] fusion(...)``).
So ``op_map`` compiles the cell's program as ``run.py`` builds it
(``run.timed_program(cfg, run.entry_point(cfg))``, lowered on the shapes
of the adapter's parameters and of one batch of the traffic, after the
window and with the persistent cache off) and reads each ENTRY
instruction's name, output dimensions and ``op_name`` from the optimized
HLO text.  An operation's layer is the first component of its
``op_name`` path that names a layer; operations in no layer scope (the
prefetch of weights, the input's relayout) are *unscoped* and in no group.

The map is only trusted if it is the program that ran: every operation of
the traced window has to be in it, with the same output dimensions.
Otherwise the readers here return ``None``; they never guess.

**HBM bytes.**  The trace's event name holds the instruction with the
types of its result and operands, layouts included.  ``hbm_bytes`` counts:

* a buffer only where it lies in HBM (memory space 0: no ``S(n)`` in its
  layout);
* an async transfer once: its ``-start`` moves the destination buffer's
  bytes, once for each end of the transfer that is in HBM (a weight
  prefetched from HBM into VMEM counts once); its ``-done`` counts
  nothing;
* any other operation (a fusion, a custom-call, a copy, ...): each HBM
  operand once and each HBM result once;
* logical bytes, elements times the element size, without tile padding.

What the count cannot see: the re-reads inside an operation (a Pallas
kernel that reads an operand's tiles more than once, a conv's halo rows),
and partial reads (an operand that a fusion slices is counted whole).
"""

from __future__ import annotations

import functools
import json
import re

import jax

import run as harness

UNSCOPED = "(unscoped)"

_ARRAY = re.compile(r"\b([a-z]\w*)\[([\d,]*)\](\{[^}]*\})?")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", re.S)
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_BITS = {"pred": 8, "s4": 4, "u4": 4, "s8": 8, "u8": 8, "s16": 16, "u16": 16,
         "f16": 16, "bf16": 16, "s32": 32, "u32": 32, "f32": 32, "s64": 64,
         "u64": 64, "f64": 64, "c64": 64, "c128": 128}


# --- instruction text -------------------------------------------------------

def _balanced(s: str, i: int) -> int:
    """Index just past the bracket group that opens at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] in "([{":
            depth += 1
        elif s[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    raise ValueError(f"unbalanced instruction text: {s[:120]!r}")


def _top_level(s: str) -> list[str]:
    """The comma-separated elements of a tuple type ``(a, b, ...)``."""
    out, depth, start = [], 0, 1
    for j, ch in enumerate(s):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 1:
            out.append(s[start:j])
            start = j + 1
    out.append(s[start:-1])
    return [e.strip() for e in out]


@functools.cache
def split_instruction(text: str) -> tuple[str, str, str, str]:
    """(name, result type, opcode, operand list) of one HLO instruction,
    as printed in optimized HLO text or in a TPU trace's event name."""
    m = _INSTR.match(text)
    if not m:
        raise ValueError(f"not an HLO instruction: {text[:120]!r}")
    name, rest = m.groups()
    if rest.startswith("("):
        end = _balanced(rest, 0)
    else:
        array = _ARRAY.match(rest)
        if array is None:
            raise ValueError(f"no result type: {text[:120]!r}")
        end = array.end()
    result, rest = rest[:end], rest[end:].lstrip()
    opcode = re.match(r"[\w\-]*", rest).group(0)
    if not opcode:
        raise ValueError(f"no opcode: {text[:120]!r}")
    args = rest[len(opcode):]
    return name, result, opcode, args[:_balanced(args, 0)] if args else ""


def out_dims(result: str) -> tuple[int, ...]:
    """Dimensions of the first array of a result type."""
    m = _ARRAY.search(result)
    return tuple(int(d) for d in m.group(2).split(",") if d) if m else ()


def _arrays(t: str) -> list[tuple[int, bool]]:
    """(logical bytes, in HBM) of each array in a type or operand list."""
    out = []
    for dtype, dims, layout in _ARRAY.findall(t):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((n * _BITS.get(dtype, 0) // 8, "S(" not in layout))
    return out


def _hbm(t: str) -> int:
    return sum(b for b, in_hbm in _arrays(t) if in_hbm)


@functools.cache
def hbm_bytes(text: str) -> int:
    """HBM bytes one run of the instruction moves (module docstring)."""
    _, result, opcode, args = split_instruction(text)
    if opcode.endswith("-done"):
        return 0
    if opcode.endswith("-start"):
        parts = _top_level(result) if result.startswith("(") else []
        if len(parts) < 2:
            raise ValueError(f"an async start without its buffers: {text[:120]!r}")
        dest = parts[0] if opcode == "copy-start" else parts[1]
        moved = sum(b for b, _ in _arrays(dest))
        ends = any(h for _, h in _arrays(args)) + any(h for _, h in _arrays(dest))
        return moved * ends
    operands = {}
    for operand in _top_level(args) if args else []:
        ref = operand.rsplit("%", 1)
        operands[ref[-1] if len(ref) == 2 else operand] = ref[0]
    return sum(_hbm(t) for t in operands.values()) + _hbm(result)


# --- the map from instruction to layer ---------------------------------------

def entry_map(hlo_text: str, layers) -> dict[str, tuple[tuple[int, ...], str | None]]:
    """Instruction name -> (output dimensions, layer or ``None``) of every
    instruction of the ENTRY computation in optimized HLO text."""
    layers = set(layers)
    start = hlo_text.index("\nENTRY ")
    body = hlo_text[hlo_text.index("\n", start + 1) + 1:]
    out = {}
    for line in body.splitlines():
        if line.strip() == "}":
            break
        name, result, _, _ = split_instruction(line)
        m = _OP_NAME.search(line)
        path = m.group(1).split("/") if m else []
        out[name] = (out_dims(result),
                     next((c for c in path if c in layers), None))
    return out


def layer_names(cfg: dict) -> list[str]:
    return [part for parts in cfg["groups"].values() for part in parts]


def program_text(cfg: dict, adapter, batch: int) -> str:
    """Optimized HLO text of the cell's program, compiled as ``run.py``
    compiles it but with the persistent cache off: the cache's key leaves
    out metadata, so an executable from it may carry the op names of
    another build of the same program (or no HLO text at all)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    key = jax.random.key(0)
    params = jax.eval_shape(lambda k: adapter.init_params(cfg, k), key)
    x = jax.eval_shape(lambda k: adapter.init_inputs(cfg, k, batch), key)
    lowered = harness.timed_program(cfg, harness.entry_point(cfg)).lower(params, x)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return lowered.compile().as_text() or ""
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


_MAPS: dict[str, dict] = {}


def op_map(run) -> dict[str, tuple[tuple[int, ...], str | None]]:
    """The map of the run's program, compiled once per configuration and
    batch in a process."""
    key = json.dumps([run.cfg, run.traffic["batch"]], sort_keys=True)
    if key not in _MAPS:
        _MAPS[key] = entry_map(
            program_text(run.cfg, run.adapter, run.traffic["batch"]),
            layer_names(run.cfg))
    return _MAPS[key]


# --- readings ----------------------------------------------------------------

def _events(run):
    """(name, text, seconds) of every device operation in the trace, each
    checked against the map; ``None`` if there is none, or if one is not in
    the map with its output dimensions."""
    evs = [e for device in run.trace.ops.values() for e in device]
    if not evs:
        return None
    out = []
    try:
        ops = op_map(run)
        for text, s, e in evs:
            name, result, _, _ = split_instruction(text)
            if name not in ops or ops[name][0] != out_dims(result):
                return None
            out.append((name, text, (e - s) / 1e9))
    except ValueError:      # text that is not an HLO instruction
        return None
    return out


def group_seconds(run) -> dict[str, float] | None:
    """Device seconds of each group's operations, and of the unscoped ones
    under ``UNSCOPED``, averaged over devices as ``Reduced.op_seconds``."""
    evs = _events(run)
    if evs is None:
        return None
    group_of = {part: g for g, parts in run.cfg["groups"].items()
                for part in parts}
    ops = op_map(run)
    out = dict.fromkeys([*run.cfg["groups"], UNSCOPED], 0.0)
    for name, _, sec in evs:
        out[group_of.get(ops[name][1], UNSCOPED)] += sec
    n = max(1, len(run.trace.ops))
    return {g: sec / n for g, sec in out.items()}


def group_roofline(run, group: str) -> float | None:
    """The group's share of its roofline, in percent: the least time its
    work in the traced window could take (the larger of its FLOPs over the
    peak at the configuration's precision and its minimal bytes over HBM
    bandwidth, per query, times the queries) over the device time of the
    operations in its layers' scopes."""
    seconds = group_seconds(run)
    queries = run.traced.attempted
    if seconds is None or seconds[group] <= 0 or queries == 0:
        return None
    work = run.adapter.group_work(run.cfg, group, run.traffic["batch"])
    least = max(work["flops"] / run.flops_peak,
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * queries / seconds[group]


def window_hbm_bytes(run) -> int | None:
    """HBM bytes of every device operation in the traced window, summed
    over devices."""
    evs = _events(run)
    if evs is None:
        return None
    try:
        return sum(hbm_bytes(text) for _, text, _ in evs)
    except ValueError:
        return None
