"""Model step, decode: device time a decode step (ms) of the ops under
the program's ``moe`` named scope: the router, the held experts and the
shared expert.

The program gives the compiled HLO of the decode programs it launched
(``repro.serve.tracing.compiled_texts``); which of their ops count is
decided here.  An op is under a scope where its op metadata's name path
holds the scope; a fusion (or call) is where its root instruction is, so
a fusion that only takes a scoped value in, or holds one scoped
instruction among others, counts where its result is made.  The
trace's ops inside the harness's ``decode_step`` spans are matched by
instruction name.  None where no op carries the scope, or where the
program gives no compiled text (a program older than the scopes); the
``mla`` scope's time is noted beside it."""

import bisect
import re

from harness import trace

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def op_scopes(text: str) -> dict:
    """{instruction name: its op_name path} of compiled HLO ``text``, a
    fusion's or call's being its root instruction's (its own where the
    root has none, as a multi-output fusion's tuple)."""
    comp, own, callee, root = None, {}, {}, {}
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        c = _CALLS.search(line)
        if c:
            callee[name] = c.group(1)
        if m.group(1):
            root[comp] = name

    def scope(name, depth=0):
        if name in callee and callee[name] in root and depth < 16:
            inner = scope(root[callee[name]], depth + 1)
            if inner:
                return inner
        return own.get(name, "")
    return {name: scope(name) for name in own}


def scope_ops(texts, scope: str) -> set:
    """The instruction names of ``texts`` whose path holds ``scope``."""
    out = set()
    for text in texts:
        out |= {n for n, path in op_scopes(text).items()
                if scope in path.split("/")}
    return out


def _scoped_ns(run, names):
    """(device ns of the ops named in ``names`` that start inside a
    ``decode_step`` span, the number of those spans)."""
    spans = sorted((s, s + d) for n, s, d in run.events["host"]
                   if n == "decode_step")
    starts = [a for a, _ in spans]
    total = 0
    for name, s, d in run.events["device"]:
        if name.split(" ")[0] not in names:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            total += d
    return total, len(spans)


def read(run):
    if run.events is None:
        return None
    from repro.serve import tracing
    if not hasattr(tracing, "compiled_texts"):
        run.notes.append("moe.decode_ms: the program gives no compiled "
                         "text of its launches")
        return None
    texts = tracing.compiled_texts("decode")
    if not texts:
        raise RuntimeError("moe.decode_ms: the program noted no decode "
                           "program, though decode steps ran")
    moe, mla = scope_ops(texts, "moe"), scope_ops(texts, "mla")
    if not moe:
        return None
    moe_ns, steps = _scoped_ns(run, moe)
    mla_ns, _ = _scoped_ns(run, mla)
    if not steps:
        return None
    busy = trace.busy_ns(run.events)
    run.notes.append(
        f"decode scopes: moe {moe_ns / 1e9:.6f} s, mla {mla_ns / 1e9:.6f} s "
        f"over {steps} decode steps, of {busy / 1e9:.6f} s busy")
    return moe_ns / 1e6 / steps
