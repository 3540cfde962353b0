"""Reading a profiler trace: ``.xplane.pb`` through
``jax.profiler.ProfileData``, reduced to plain event lists, and the
interval arithmetic the per-layer metrics share.

A trace here is a dict

    {"host":    [[name, start_ns, end_ns], ...],      # bench.* spans
     "devices": {plane: {"ops": [[name, s, e], ...],
                         "modules": [[name, s, e], ...]}}}

so a test can hand the metrics a small recorded trace as JSON.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_WORDS = ("all-gather", "all-reduce", "all-to-all",
                    "collective-permute", "reduce-scatter", "send", "recv")


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"host": [], "devices": {}}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.end_ns]
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX)]
        elif plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:       # not a chip (host, trace
                continue                    # planes such as Megascale)
            dev = {}
            for key, name in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                ln = lines.get(name)
                dev[key] = [] if ln is None else [
                    [e.name, e.start_ns, e.end_ns] for e in ln.events]
            out["devices"][plane.name] = dev
    return out


def describe(trace_dir: str, limit: int = 8) -> list:
    """Plane and line names with a few event names each: what to look
    at by hand before trusting the reduction."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    rows = []
    for plane in data.planes:
        for line in plane.lines:
            evs = list(line.events)
            rows.append([plane.name, line.name, len(evs),
                         sorted({e.name for e in evs})[:limit]])
    return rows


def window(trace: dict, name: str = "bench.window"):
    """(start, end) in ns of the harness's window span."""
    spans = [(s, e) for n, s, e in trace["host"] if n == name]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def clip(events, lo, hi) -> list:
    """[(start, end)] of the events, cut to [lo, hi]; empty ones go."""
    out = []
    for ev in events:
        s, e = max(ev[1], lo), min(ev[2], hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list:
    """Merged, sorted, disjoint intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """The parts of disjoint sorted intervals ``a`` not covered by
    disjoint sorted intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_name(name: str) -> str:
    """An XLA op event's own name and result type, without its operands
    and layouts: ``%fusion.3 = bf16[8,128]{1,0} fusion(...)`` reads
    ``fusion.3 bf16[8,128]``."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not rest:
        return head
    kind = rest.split("{")[0].split(" ")[0]
    return f"{head} {kind}"


def is_collective(name: str) -> bool:
    """Whether the op itself (not an operand it reads) is a collective."""
    low = name.partition(" = ")[0].lower()
    return any(w in low for w in COLLECTIVE_WORDS)


def busy(trace: dict, win) -> dict:
    """Busy ns per device inside the window: the union of its ops."""
    lo, hi = win
    return {d: length(union(clip(v["ops"], lo, hi)))
            for d, v in trace["devices"].items()}


def module_times(trace: dict, module: str, win) -> dict:
    """Per device: durations (ns) of the executions of XLA module
    ``module`` (its events are named ``module`` or ``module(<id>)``)
    that lie inside the window."""
    lo, hi = win
    out = {}
    for d, v in trace["devices"].items():
        out[d] = [e - s for name, s, e in v["modules"]
                  if (name == module or name.startswith(module + "("))
                  and s >= lo and e <= hi]
    return out


def exposed_collective(trace: dict, win) -> dict:
    """Per device: ns in which a collective op runs and no other op."""
    lo, hi = win
    out = {}
    for d, v in trace["devices"].items():
        coll = union(clip([e for e in v["ops"] if is_collective(e[0])],
                          lo, hi))
        other = union(clip([e for e in v["ops"]
                            if not is_collective(e[0])], lo, hi))
        out[d] = length(subtract(coll, other))
    return out


def top_ops(trace: dict, win, limit: int = 10) -> list:
    """[[op name, seconds]] of the ops that took most time, summed over
    devices and divided by their number."""
    lo, hi = win
    tot: dict = {}
    for v in trace["devices"].values():
        for name, s, e in v["ops"]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = op_name(name)
                tot[key] = tot.get(key, 0.0) + (e - s)
    ndev = max(1, len(trace["devices"]))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / ndev / 1e9] for name, ns in best]


def idle_gaps(trace: dict, win, limit: int = 10) -> list:
    """[[what the host was doing, seconds]] for the longest idle gaps of
    the first device inside the window.  The host activity is the
    innermost ``bench.*`` span (other than the window) that covers the
    gap's middle, or "untraced"."""
    if not trace["devices"]:
        return []
    lo, hi = win
    dev = trace["devices"][sorted(trace["devices"])[0]]
    busy_iv = union(clip(dev["ops"], lo, hi))
    gaps = subtract([(lo, hi)], busy_iv)
    spans = [(s, e, n) for n, s, e in trace["host"]
             if n != "bench.window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:limit]:
        mid = (s + e) / 2
        cover = [(se - ss, n) for ss, se, n in spans if ss <= mid <= se]
        out.append([min(cover)[1] if cover else "untraced",
                    (e - s) / 1e9])
    return out
