"""Device time by the program's own scopes: the pieces of the reduction
that ``bench/trace_reduce.py`` does not have yet.

The program names its device work with ``jax.named_scope``, all
``repro.*``: the bucket engine's gather, sweep and scatter, the pass's
pair and box steps, the chunk's probe and objective. Here:

  * a device op's scope is the deepest ``repro.*`` component of its
    ``tf_op`` (JAX's op_name path), read from the ``.trace.json.gz`` the
    profiler writes beside the ``.xplane.pb`` (``tf_ops``) and joined by
    the op's instruction text;
  * an op whose own metadata lost the scope takes the one scope of the
    instructions its fusion wraps, read from the compiled window
    program's HLO text (``hlo_scopes``). The TPU compiler rewrites the
    bucket engine's scatters into X as new fusions that carry no scope;
  * ``reduce`` is ``trace_reduce.reduce`` plus device seconds by scope.
    Given the program's ``repro.*`` host spans beside ``bench.*``, its
    idle gaps go to the innermost span of either prefix.

Nothing runs this module yet: ``bench/run.py`` deletes the trace before
the metric readers run and hands them only its summary, and
``trace_reduce.from_xplane`` keeps only ``bench.*`` spans. Once a
benchmark change hands the readers the trace directory and the window
program's HLO text, and ``from_xplane`` keeps ``repro.*`` spans, these
functions move into ``trace_reduce`` and this module goes.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re

from bench import trace_reduce as tr

PREFIX = "repro."
#: the device time no ``repro.*`` scope claims
UNSCOPED = "(no scope)"


def scope_of(op_name: str | None) -> str | None:
    """The deepest ``repro.*`` component of an op_name path."""
    parts = [p for p in (op_name or "").split("/") if p.startswith(PREFIX)]
    return parts[-1].rstrip(":") if parts else None


_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def hlo_scopes(text: str) -> dict[str, str]:
    """Instruction name -> scope, for each instruction of a compiled
    program's HLO text whose own metadata has no ``repro.*`` scope but
    whose called computation's instructions all carry one and the same."""
    comps, calls, own = collections.defaultdict(set), {}, {}
    cur = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(1)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if cur is None or not m:
            continue
        md = _OP_NAME.search(line)
        sc = scope_of(md.group(1)) if md else None
        if sc:
            comps[cur].add(sc)
        own[m.group(1)] = sc
        c = _CALLS.search(line)
        if c:
            calls[m.group(1)] = c.group(1)
    return {name: next(iter(comps[callee]))
            for name, callee in calls.items()
            if own.get(name) is None and len(comps.get(callee, ())) == 1}


def tf_ops(trace_dir: str) -> dict[str, str]:
    """Instruction text -> ``tf_op`` of every device op event in the
    ``.trace.json.gz`` files under ``trace_dir``."""
    out = {}
    for path in glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                          recursive=True):
        with gzip.open(path) as f:
            events = json.load(f).get("traceEvents", [])
        for ev in events:
            args = ev.get("args") or {}
            if "long_name" in args and "tf_op" in args:
                out[args["long_name"]] = args["tf_op"]
    return out


def op_scope(name: str, tf_op: dict, hlo: dict) -> str:
    sc = scope_of(tf_op.get(name))
    if sc is None:
        sc = hlo.get(name.split(" = ", 1)[0].lstrip("%"))
    return sc or UNSCOPED


def reduce(device_ops, spans, tf_op: dict, hlo: dict, top=10):
    """``trace_reduce.reduce``, plus ``scope_s`` (device seconds by scope,
    averaged over devices, with the unclaimed time under ``UNSCOPED``),
    and ``device_ops`` labels that carry their scope
    (``repro.bucket.gather/fusion.341 f32[147456] kCustom``) with the
    same seconds. None where ``trace_reduce.reduce`` finds nothing."""
    summary = tr.reduce(device_ops, spans, top=top)
    if summary is None:
        return None
    lo, hi = next((s, s + d) for n, s, d in spans if n == tr.WINDOW_SPAN)
    by_scope, by_label = collections.Counter(), collections.Counter()
    for name, s, d, _ in device_ops:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a or tr.opcode(name) in tr.CONTROL_FLOW:
            continue
        sc = op_scope(name, tf_op, hlo)
        by_scope[sc] += b - a
        by_label[f"{sc}/{tr.short(name)}"] += b - a
    k = 1e-9 / summary["devices"]
    summary["scope_s"] = {s: v * k for s, v in by_scope.items()}
    summary["device_ops"] = [[n, v * k] for n, v in by_label.most_common(top)]
    return summary
