"""Device time and roofline share of a model's parts by kind: the
operations whose ``op_name`` holds a kind's scope, wherever in the network
they are (for ConvNeXt, ``dwconv`` and ``mlp`` inside every block).

The adapter names its kinds (``KINDS``) and gives each kind's work for one
batch (``kind_work(cfg, kind, batch)``: FLOPs and minimal HBM bytes).  The
map from operation to kind is ``scopes.py``'s, built with the kinds as the
layer set: the cell's program compiled again as ``run.py`` compiles it, and
each ENTRY instruction's first ``op_name`` component that names a kind.  As
there, the map is trusted only if every operation of the traced window is
in it with its output dimensions; otherwise the readers return ``None``.
What XLA fuses across a scope's edge is counted where the fusion's own
``op_name`` puts it.
"""

from __future__ import annotations

import json

import scopes

_MAPS: dict[str, dict] = {}


def kind_map(run) -> dict[str, tuple[tuple[int, ...], str | None]]:
    """Instruction name -> (output dimensions, kind or ``None``) of the
    run's program, compiled once per configuration and batch in a
    process."""
    key = json.dumps([run.cfg, run.traffic["batch"]], sort_keys=True)
    if key not in _MAPS:
        _MAPS[key] = scopes.entry_map(
            scopes.program_text(run.cfg, run.adapter, run.traffic["batch"]),
            run.adapter.KINDS)
    return _MAPS[key]


def kind_seconds(run, kind: str) -> float | None:
    """Device seconds of the kind's operations in the traced window,
    averaged over devices; ``None`` if the trace has no operation or one
    that is not in the map with its output dimensions."""
    evs = [e for device in run.trace.ops.values() for e in device]
    if not evs:
        return None
    total = 0.0
    try:
        ops = kind_map(run)
        for text, s, e in evs:
            name, result, _, _ = scopes.split_instruction(text)
            if name not in ops or ops[name][0] != scopes.out_dims(result):
                return None
            if ops[name][1] == kind:
                total += (e - s) / 1e9
    except ValueError:      # text that is not an HLO instruction
        return None
    return total / len(run.trace.ops)


def kind_roofline(run, kind: str) -> float | None:
    """The kind's share of its roofline, in percent: the least time its
    work in the traced window could take (the larger of its FLOPs over the
    peak at the configuration's precision and its minimal bytes over HBM
    bandwidth, per query, times the queries) over the device time of its
    operations."""
    seconds = kind_seconds(run, kind)
    queries = run.traced.attempted
    if not seconds or queries == 0:
        return None
    work = run.adapter.kind_work(run.cfg, kind, run.traffic["batch"])
    least = max(work["flops"] / run.flops_peak,
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * queries / seconds
