"""The device trace: capture it, cut it to plain events, reduce it.

``capture`` wraps the measured window in ``jax.profiler`` tracing into a
temporary directory; ``events`` reads the ``.xplane.pb`` it wrote into a
plain dict of

* ``device``: ``[name, start_ns, dur_ns]`` for every op on the first
  device's ``XLA Ops`` line (the chip's own timeline),
* ``host``: ``[name, start_ns, dur_ns]`` for the harness's own host spans
  (``window``, ``scheduler``, ``generator``, ``prefill_chunk``,
  ``decode_step``),

and the reductions below work on that dict alone, so a small recorded
trace can be kept as a JSON file and checked.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile

HOST_SPANS = ("window", "scheduler", "generator", "prefill_chunk",
              "decode_step")


@contextlib.contextmanager
def capture():
    """Trace the block; yields a dict that holds the events afterwards."""
    import jax
    from jax.profiler import TraceAnnotation
    out: dict = {}
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            with TraceAnnotation("window"):
                yield out
        finally:
            jax.profiler.stop_trace()
        out.update(events(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def op_label(text: str) -> str:
    """``"<instruction> <opcode>"`` from an op's HLO text, with a custom
    call's target after a colon: ``"%checkpoint.9 = bf16[..] custom-call(
    ..), custom_call_target="tpu_custom_call""`` becomes
    ``"checkpoint.9 custom-call:tpu_custom_call"`` (a Pallas kernel)."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    if rest.startswith("("):            # tuple shape: skip balanced parens
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    opcode = rest.strip().split("(", 1)[0]
    if opcode == "custom-call":
        m = re.search(r'custom_call_target="([^"]+)"', text)
        if m:
            opcode += ":" + m.group(1)
    return f"{name.lstrip('%')} {opcode}"


def events(trace_dir: str) -> dict:
    """The plain events of the ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[0])
    device, host, planes = [], [], []
    for plane in pd.planes:
        planes.append(plane.name)
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
        elif re.match(r"/device:TPU:0$", plane.name):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    device.append([op_label(e.name), int(e.start_ns),
                                   int(e.duration_ns)])
    device.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return {"device": device, "host": host, "planes": planes}


def window_of(ev: dict) -> tuple:
    """(start_ns, end_ns) of the traced window (the ``window`` span)."""
    w = [h for h in ev["host"] if h[0] == "window"]
    if not w:
        raise ValueError("trace holds no 'window' span")
    return w[0][1], w[0][1] + w[0][2]


def busy_intervals(ev: dict) -> list:
    """The union of device-op intervals inside the window, merged."""
    w0, w1 = window_of(ev)
    spans = sorted((max(s, w0), min(s + d, w1)) for _, s, d in ev["device"]
                   if s + d > w0 and s < w1)
    merged: list = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(ev: dict) -> int:
    return sum(e - s for s, e in busy_intervals(ev))


def window_ns(ev: dict) -> int:
    w0, w1 = window_of(ev)
    return w1 - w0


def kernel_ns(ev: dict, pattern: str, within: str) -> tuple:
    """(total device ns, event count) of the ops whose label matches
    ``pattern`` and that start inside a host span named ``within``
    (``decode_step`` or ``prefill_chunk``: the traced run waits for
    each launch inside its span, so its device ops fall in it)."""
    rx = re.compile(pattern)
    spans = [(s, s + d) for name, s, d in ev["host"] if name == within]
    starts = [s for s, _ in spans]
    total = count = 0
    for name, s, d in ev["device"]:
        if not rx.search(name):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            total += d
            count += 1
    return total, count


#: ops that only contain others on the same line (their time is their
#: body's), left out of the top list
CONTAINERS = re.compile(r" (while|conditional|call)$")


def top_ops(ev: dict, n: int = 10) -> list:
    """The ``n`` device ops (by name) that took the most time."""
    w0, w1 = window_of(ev)
    tot: dict = {}
    for name, s, d in ev["device"]:
        if w0 <= s < w1 and not CONTAINERS.search(name):
            tot[name] = tot.get(name, 0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(ev: dict, n: int = 10) -> list:
    """The ``n`` longest idle gaps of the device inside the window, each
    named by the innermost harness host span around its midpoint
    (``idle`` where none is)."""
    w0, w1 = window_of(ev)
    busy = busy_intervals(ev)
    gaps = []
    prev = w0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    spans = [h for h in ev["host"] if h[0] != "window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        inner = [h for h in spans if h[1] <= mid < h[1] + h[2]]
        name = min(inner, key=lambda h: h[2])[0] if inner else "idle"
        out.append([name, (e - s) / 1e9])
    return out


def cut(ev: dict, start_ns: int, end_ns: int) -> dict:
    """The events inside [start_ns, end_ns), with a ``window`` span over
    exactly that interval (how the checked-in test trace was made)."""
    def inside(es):
        return [e for e in es if start_ns <= e[1] < end_ns
                and e[0] != "window"]
    return {"device": inside(ev["device"]),
            "host": [["window", start_ns, end_ns - start_ns]]
            + inside(ev["host"]),
            "planes": ev.get("planes", [])}
