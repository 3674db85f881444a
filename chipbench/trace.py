"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the metric
readers need, through ``jax.profiler.ProfileData`` alone.

    python3 chipbench/trace.py dump <file.xplane.pb>      planes, lines, top events
    python3 chipbench/trace.py reduce <file.xplane.pb>    the reduction, as JSON
    python3 chipbench/trace.py fixture <file.xplane.pb> <out.trace.json.gz> <steps>
                                   the first steps' events and their reduction,
                                   for chipbench/fixtures (the tests repeat it)

A TPU trace holds one plane a chip (``/device:TPU:<n>``) with a line
``XLA Ops`` (one event an executed HLO instruction or kernel), a line
``XLA Modules`` (one event an executed program) and a line ``Steps``; and
host planes whose lines are threads, on which the harness's own spans
(``chipbench.step``, ``chipbench.fetch``) lie.  All on one clock, in ns.

The traced window is the host's: from the start of the first ``step`` span
to the end of the last ``fetch`` span.  Device events are clipped to it.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_SPANS = ("chipbench.step", "chipbench.fetch")
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute)")


def find(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError("no *.xplane.pb under %s" % trace_dir)
    return files[-1]


def load(path):
    """{"devices": {n: {"ops": [(start, end, name)], "modules": [...]}},
    "host": [(start, end, name)]} with times in ns, sorted by start."""
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events if e.name in HOST_SPANS)
    host.sort()
    return {"devices": devices, "host": host}


def union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events, lo, hi):
    return [(max(s, lo), min(e, hi)) + tuple(rest)
            for s, e, *rest in events if e > lo and s < hi]


def covered(intervals, merged):
    """ns of ``intervals`` (disjoint) that lie inside ``merged``."""
    total, j = 0, 0
    for s, e in intervals:
        while j < len(merged) and merged[j][1] <= s:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < e:
            total += min(e, merged[k][1]) - max(s, merged[k][0])
            k += 1
    return total


def short(name):
    """The instruction's own name: an ``XLA Ops`` event is named by the
    whole HLO line, ``%fusion.3 = bf16[...] fusion(...), kind=kCustom``."""
    return name.split(" = ", 1)[0].lstrip("%")


def kernel_base(name):
    """A short name without the instance suffix the compiler adds
    (``convolution_add_fusion.57`` -> ``convolution_add_fusion``,
    ``transpose_jvp_flash_bwd_dkv__.14`` -> ``transpose_jvp_flash_bwd_dkv__``)."""
    return re.sub(r"[.\d]+$", "", short(name))


def group(name):
    """The key under which the breakdown sums an event: instances of one
    named fusion or kernel together; an anonymous ``fusion.N`` alone, with
    the shape it makes, since unrelated ones share that name."""
    base = kernel_base(name)
    if base != "fusion":
        return base
    shape = re.match(r"^\S+ = \(?([a-z0-9]+\[[\d,]*\])", name)
    return "%s %s" % (short(name), shape.group(1) if shape else "")


def op_class(name):
    """What kind of work an ``XLA Ops`` event is, from its HLO line: a
    fusion by its kind (on a TPU ``kOutput`` fusions are the matrix
    products and convolutions, ``kLoop`` elementwise, ``kInput`` reductions,
    ``kCustom`` gathers, scatters and the like), anything else by opcode."""
    kind = re.search(r"kind=k(\w+)", name)
    if kind:
        return {"Output": "matmul_conv_fusion", "Loop": "loop_fusion",
                "Input": "reduce_fusion",
                "Custom": "gather_scatter_fusion"}.get(kind.group(1),
                                                       kind.group(1))
    op = re.search(r"\s([a-z][a-z0-9_-]*)\(", name.split(" = ", 1)[-1])
    return op.group(1) if op else "other"


def host_span_at(host, t):
    inside = [n for s, e, n in host if s <= t < e]
    return inside[-1] if inside else "between_spans"


def reduce(loaded):
    """The numbers every reader shares; times in seconds."""
    host = loaded["host"]
    steps = [h for h in host if h[2] == HOST_SPANS[0]]
    if not steps:
        raise ValueError("the trace holds no %s span" % HOST_SPANS[0])
    lo, hi = steps[0][0], max(e for _s, e, _n in host)
    window_s = (hi - lo) / 1e9
    per_device = {}
    for n, dev in sorted(loaded["devices"].items()):
        ops = clip(dev["ops"], lo, hi)
        busy = union((s, e) for s, e, _ in ops)
        busy_ns = sum(e - s for s, e in busy)
        sums, counts, classes, coll, rest = {}, {}, {}, [], []
        for s, e, name in ops:
            key, cls = group(name), op_class(name)
            sums[key] = sums.get(key, 0) + (e - s)
            counts[key] = counts.get(key, 0) + 1
            classes[cls] = classes.get(cls, 0) + (e - s)
            (coll if COLLECTIVE.match(key) else rest).append((s, e))
        gaps = [(b[0] - a[1], a[1]) for a, b in zip(busy, busy[1:])]
        modules = clip(dev["modules"], lo, hi)
        names = {}
        for s, e, name in modules:
            names[name] = names.get(name, 0) + (e - s)
        # the step program is the module that takes most of the time
        step_name = max(names, key=names.get) if names else None
        step_mods = [(s, e) for s, e, name in modules if name == step_name]
        launch = [(b[0] - a[1]) / 1e6 for a, b in zip(step_mods, step_mods[1:])]
        others, coll_u = union(rest), union(coll)
        coll_ns = sum(e - s for s, e in coll_u)
        per_device[n] = {
            "busy_s": busy_ns / 1e9,
            "op_seconds": {k: v / 1e9 for k, v in sums.items()},
            "op_counts": counts,
            "class_seconds": {k: v / 1e9 for k, v in classes.items()},
            "gaps": sorted(gaps, reverse=True)[:10],
            "step_module": step_name, "step_count": len(step_mods),
            "step_module_s": sum(e - s for s, e in step_mods) / 1e9,
            "launch_gaps_ms": launch,
            "collective_s": coll_ns / 1e9,
            "collective_exposed_s":
                (coll_ns - covered(coll_u, others)) / 1e9,
        }
    if not per_device:
        raise ValueError("the trace holds no /device:TPU plane")
    fullest = max(per_device, key=lambda n: per_device[n]["busy_s"])
    d = per_device[fullest]
    top = sorted(d["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    gaps = [["%s@%.3fs" % (host_span_at(host, at), (at - lo) / 1e9), g / 1e9]
            for g, at in d["gaps"]]
    return {
        "window_s": window_s,
        "busy_s": sum(p["busy_s"] for p in per_device.values())
        / len(per_device),
        "fullest": fullest, "devices": per_device,
        "host_steps": len(steps),
        "breakdown": {"device_ops": [[k, v] for k, v in top],
                      "idle_gaps": gaps},
    }


def reduce_dir(trace_dir, n_devices):
    out = reduce(load(find(trace_dir)))
    if len(out["devices"]) != n_devices:
        raise ValueError("traced %d device(s), the cell uses %d"
                         % (len(out["devices"]), n_devices))
    return out


def dump(path, top=40):
    """What the file holds, for reading by hand before trusting ``load``."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print("PLANE %r: %d lines" % (plane.name, len(lines)))
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            sums = {}
            for e in events:
                k = group(e.name)
                a = sums.setdefault(k, [0, 0.0, e])
                a[0] += 1
                a[1] += e.duration_ns
            span = (min(e.start_ns for e in events),
                    max(e.start_ns + e.duration_ns for e in events))
            print("  LINE %r: %d events, %.3f ms .. %.3f ms" % (
                line.name, len(events), span[0] / 1e6, span[1] / 1e6))
            if plane.name.startswith("/host:") and \
                    not any(e.name in HOST_SPANS for e in events):
                continue
            for k, (n, ns, e) in sorted(sums.items(),
                                        key=lambda kv: -kv[1][1])[:top]:
                stats = {str(a): str(b)[:80] for a, b in e.stats}
                print("    %-40s x%-6d %10.3f ms  e.g. %r %s" % (
                    k[:40], n, ns / 1e6, e.name[:60],
                    json.dumps(stats)[:400]))


def fixture(path, out, n_steps):
    """Trim a recorded trace to its first ``n_steps`` steps and keep the
    events beside what ``reduce`` makes of them."""
    import gzip

    loaded = load(path)
    steps = [h for h in loaded["host"] if h[2] == HOST_SPANS[0]]
    lo, hi = steps[0][0], steps[n_steps][0]
    loaded["host"] = [h for h in loaded["host"] if lo <= h[0] and h[1] <= hi]
    for dev in loaded["devices"].values():
        for key in dev:
            dev[key] = [e for e in dev[key] if lo <= e[0] and e[1] <= hi]
    with gzip.open(out, "wt") as f:
        json.dump({"source": os.path.basename(path), "steps": n_steps,
                   "loaded": loaded, "reduced": reduce(loaded)}, f)


if __name__ == "__main__":
    if len(sys.argv) < 3 or sys.argv[1] not in ("dump", "reduce", "fixture"):
        raise SystemExit(__doc__)
    if sys.argv[1] == "dump":
        dump(sys.argv[2])
    elif sys.argv[1] == "reduce":
        print(json.dumps(reduce(load(sys.argv[2])), indent=1))
    else:
        fixture(sys.argv[2], sys.argv[3], int(sys.argv[4]))
