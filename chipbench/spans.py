"""The program's own spans and scopes, read from the profiler's trace.

    python3 chipbench/spans.py report <file.xplane.pb>    span table, scope table, clock check, idle gaps
    python3 chipbench/spans.py fixture <file.xplane.pb> <out.spans.json.gz> <steps>
                                   the first steps' events and their summary,
                                   for chipbench/fixtures (the tests repeat it)

What the program writes (``mxnet_tpu/parallel/__init__.py``, through
``mx.trace.span``, which opens a ``jax.profiler.TraceAnnotation``):

- host spans, on the thread that called ``FusedTrainer.step``: ``mx.step``
  (a step annotation, ``step_num`` on the event) and its children
  ``mx.step.stage``, ``mx.step.rng``, ``mx.step.scalars``,
  ``mx.step.dispatch``; an instant ``mx.step.recompile``; ``mx.wait`` where
  ``NDArray.asnumpy`` / ``wait_to_read`` block on the device;
- device scopes, in the ``op_name`` of every HLO instruction of the step
  program: ``jvp(mx.step.forward)`` (the forward), ``transpose(jvp(
  mx.step.forward))`` (the backward, named by JAX itself) and
  ``mx.step.optimizer``.

Events come through ``jax.profiler.ProfileData``.  It shows an ``XLA Ops``
event's own stats only (offset, duration), and on a TPU no stat of the event
carries the ``op_name`` (looked at with ``trace.py dump``, PR 24): the name
stack is in the compiled HLO, which the profiler keeps in the same file, as
an ``Hlo Proto`` stat of each program's entry in the ``/host:metadata`` plane.
``hlo_op_names`` reads just that out of the file's protobuf wire format
(field numbers of ``XSpace``/``XPlane``/``XEventMetadata``/``XStat``,
``HloProto``/``HloModuleProto``/``HloComputationProto``/
``HloInstructionProto``/``OpMetadata``, unchanged for years) and maps
instruction name to ``op_name``.  Limits: an instruction is counted whole
under the scope of its own ``op_name`` — a fusion under its root's, whatever
it fused.  An instruction the compiler left without metadata takes the
``op_name`` of the computation it calls, else of the first instruction that
uses its result (``_resolved``): 15.6% of ``bert_base_t512``'s device time
and 5.0% of ``resnet50_b256``'s carry none of their own (anonymous gather and
scatter fusions, a sort, the ``-done`` halves of prefetches; parent's traces,
PR 23).  What is left after that is ``unscoped``.

Two clocks.  Host spans are on the host's clock and device events on the
device's, and the two are not aligned to a millisecond: in every trace
recorded so far the device runs a program BEFORE the host span that launches
it begins (``skew_floor_ms``, the clock check below).  So no number here
subtracts a time of one clock from a time of the other: the span metrics are
host-only, the scope metrics device-only, and idle gaps are named by the
host span at the gap's time only after the device's times are shifted by
``skew_floor_ms``.

Which trace.  A metric reader gets ``ctx`` from ``run.py``, and ``ctx``
carries no path.  ``run.py`` writes the trace under
``.chipbench_trace/<cell>/``, wipes that directory before a traced run and
removes it after the readers ran, so ``newest()`` takes the newest
``*.xplane.pb`` under ``.chipbench_trace/`` (found with ``trace.find``).
A trace without ``mx.*`` spans or scopes (the parent of PR 24) gives None for
every metric here, never 0, and nothing raises.
"""
from __future__ import annotations

import functools
import importlib.util
import os
import re
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PREFIXES = ("mx.", "chipbench.")
STEP, DISPATCH = "mx.step", "mx.step.dispatch"
FORWARD, OPTIMIZER = "mx.step.forward", "mx.step.optimizer"
BACKWARD = "transpose(jvp(%s))" % FORWARD
SCOPES = ("forward", "backward", "optimizer", "unscoped")
# which span of FusedTrainer.step launches which small device program (the
# step program itself is mx.step.dispatch's)
LAUNCHED_BY = (("jit__threefry_split(", "mx.step.rng"),
               ("jit__unstack(", "mx.step.rng"),
               ("jit_convert_element_type(", "mx.step.scalars"))
METRICS = ("step_pre_dispatch_ms", "step_dispatch_ms", "step_forward_ms",
           "step_backward_ms", "step_optimizer_ms")


@functools.lru_cache(maxsize=None)
def _trace():
    """``chipbench/trace.py``, by path: ``import trace`` could give the
    standard library's."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_trace", os.path.join(HERE, "trace.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ------------------------------------------------- the HLO's op_names
def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError("protobuf wire type %d" % kind)
        yield key >> 3, value


def _first(buf, number, default=None):
    for n, v in _fields(buf):
        if n == number:
            return v
    return default


def _packed(buf):
    """The varints of a packed repeated field."""
    i, out = 0, []
    while i < len(buf):
        value, i = _varint(buf, i)
        out.append(value)
    return out


def _instructions(module):
    """({instruction id: [name, op_name, operand ids, called computation
    ids]} in the module's order, {computation id: ids of its
    instructions, the root's last}) of an ``HloModuleProto``."""
    instructions, computations = {}, {}
    for n, computation in _fields(module):
        if n != 3:
            continue
        mine, c_id, root = [], None, None
        for n, v in _fields(computation):
            if n == 5:
                c_id = v
            elif n == 6:
                root = v
            elif n == 2:
                one, i_id = ["", "", [], []], None
                for n, w in _fields(v):
                    if n == 1:
                        one[0] = bytes(w).decode()
                    elif n == 7:
                        one[1] = bytes(_first(w, 2, b"")).decode()
                    elif n == 35:
                        i_id = w
                    elif n in (36, 38):   # repeated ids, packed or not
                        one[2 if n == 36 else 3].extend(
                            _packed(w) if isinstance(w, memoryview) else [w])
                instructions[i_id] = one
                mine.append(i_id)
        if root in mine:
            mine.remove(root)
            mine.append(root)
        computations[c_id] = mine
    return instructions, computations


def _resolved(instructions, computations):
    """{instruction name: (op_name, scopes of what it fused)}.  An instruction the compiler left
    without one takes that of the computation it calls (a fusion its
    root's, or the nearest to the root that has one; a sort its
    comparator's), else that of the first instruction that uses its result
    (a layout copy, the ``-done`` of a prefetch, a bitcast: the scope that
    waits for it)."""
    users = {}
    for i_id, (_name, _op, operands, _called) in instructions.items():
        for o in operands:
            users.setdefault(o, []).append(i_id)
    memo = {}

    def resolve(i_id, depth=0):
        if i_id in memo or i_id not in instructions or depth > 32:
            return memo.get(i_id, "")
        memo[i_id] = ""                     # no way round twice
        _name, found, _operands, called = instructions[i_id]
        if "/" not in found:    # "add", a reduction's combiner: no stack
            found = ""
        for c_id in called if not found else ():
            for inner in reversed(computations.get(c_id, ())):
                found = found or resolve(inner, depth + 1)
        for user in users.get(i_id, ()) if not found else ():
            found = found or resolve(user, depth + 1)
        memo[i_id] = found
        return found

    def fused(called):
        """Scopes of the instructions a fusion holds, as "a+b"."""
        inside = {scope_of(instructions[i][1]) for c_id in called
                  for i in computations.get(c_id, ())} - {"unscoped"}
        return "+".join(sorted(inside))

    return {one[0]: (resolve(i_id), fused(one[3]))
            for i_id, one in instructions.items()}


def hlo_op_names(path):
    """{program name as ``XLA Modules`` has it: {instruction: (op_name,
    scopes of the instructions it fused, as "backward+optimizer")}} from
    the ``Hlo Proto`` stats of the file's ``/host:metadata`` plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for n, plane in _fields(space):
        if n != 1 or bytes(_first(plane, 2, b"")) != b"/host:metadata":
            continue
        for n, entry in _fields(plane):
            if n != 4:                      # event_metadata: map<id, ...>
                continue
            meta = _first(entry, 2)
            for n, stat in _fields(meta):
                proto = _first(stat, 6) if n == 5 else None  # bytes_value
                module = _first(proto, 1) if proto is not None else None
                if module is not None:
                    out[bytes(_first(meta, 2, b"")).decode()] = _resolved(
                        *_instructions(module))
    return out


# ------------------------------------------------------------ loading
def load(path):
    """{"host": [[start, end, name, line, stats]], "devices": {n: {"ops":
    [[start, end, instruction, op_name, fused]], "modules": [[start, end,
    name]]}}}: times in ns, sorted by start; host events whose name starts
    with ``mx.`` or ``chipbench.`` with the index of their thread's line;
    device operations with the ``op_name`` the HLO of the program they ran
    in gives their instruction ("" where it gives none) and the scopes of
    the instructions a fusion holds ("backward+optimizer")."""
    from jax.profiler import ProfileData

    trace = _trace()
    op_names = hlo_op_names(path)
    host, devices, n_line = [], {}, 0
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == trace.MODULES_LINE:
                    dev["modules"] = sorted(
                        [e.start_ns, e.start_ns + e.duration_ns, e.name]
                        for e in line.events)
                elif line.name == trace.OPS_LINE:
                    dev["ops"] = sorted(
                        [e.start_ns, e.start_ns + e.duration_ns,
                         trace.short(e.name), "", ""] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                n_line += 1
                host.extend(
                    [e.start_ns, e.start_ns + e.duration_ns, e.name, n_line,
                     {k: v for k, v in e.stats}]
                    for e in line.events if e.name.startswith(PREFIXES))
    for dev in devices.values():
        modules, j = dev["modules"], 0
        for op in dev["ops"]:   # the program an operation ran in names it
            while j < len(modules) and modules[j][1] <= op[0]:
                j += 1
            if j < len(modules) and modules[j][0] <= op[0]:
                op[3:] = op_names.get(modules[j][2], {}).get(op[2], ("", ""))
    host.sort(key=lambda h: (h[0], -h[1]))
    return {"host": host, "devices": devices}


def newest():
    """The newest ``*.xplane.pb`` under ``.chipbench_trace/`` of the
    checkout, or None."""
    trace = _trace()
    base = os.path.join(ROOT, ".chipbench_trace")
    found = []
    for cell in os.listdir(base) if os.path.isdir(base) else []:
        try:
            found.append(trace.find(os.path.join(base, cell)))
        except FileNotFoundError:
            pass
    return max(found, key=os.path.getmtime) if found else None


# --------------------------------------------------------- arithmetic
def nest(spans):
    """(parent, self_ns) of host spans ``[start, end, name, line, ...]``:
    the index of the innermost span of the same line that holds each one,
    and its duration less what its children cover."""
    parent = [None] * len(spans)
    self_ns = [s[1] - s[0] for s in spans]
    stacks = {}
    for i in sorted(range(len(spans)),
                    key=lambda i: (spans[i][0], -spans[i][1])):
        start, end, _name, line = spans[i][:4]
        stack = stacks.setdefault(line, [])
        while stack and spans[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
            self_ns[stack[-1]] -= min(end, spans[stack[-1]][1]) - start
        stack.append(i)
    return parent, self_ns


def exclusive(events):
    """ns of each ``[start, end, ...]`` during which it is the event that
    started last among those running: events that nest (a ``while`` and its
    body) or overlap (an asynchronous collective and a fusion) share no
    time, so the sum is the union's length."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    owned, stack, cursor, k = [0] * len(events), [], 0, 0
    while k < len(order) or stack:
        while stack and events[stack[-1]][1] <= cursor:
            stack.pop()
        nxt = events[order[k]][0] if k < len(order) else None
        if stack and (nxt is None or events[stack[-1]][1] <= nxt):
            top = stack.pop()
            owned[top] += events[top][1] - cursor
            cursor = events[top][1]
            continue
        if nxt is None:
            break
        if stack:
            owned[stack[-1]] += max(0, nxt - cursor)
        cursor = max(cursor, nxt)
        if events[order[k]][1] > cursor:
            stack.append(order[k])
        k += 1
    return owned


def scope_of(op_name):
    """Which part of the step an operation belongs to, by its ``op_name``."""
    if BACKWARD in op_name:
        return "backward"
    if FORWARD in op_name and "transpose(" not in op_name:
        return "forward"
    if OPTIMIZER in op_name:
        return "optimizer"
    return "unscoped"


def step_modules(modules):
    """The step program's executions: those of the program that takes most
    of the device's time."""
    total = {}
    for s, e, name in modules:
        total[name] = total.get(name, 0) + (e - s)
    if not total:
        return []
    name = max(total, key=total.get)
    return [m for m in modules if m[2] == name]


def scope_seconds(dev):
    """Device seconds of each scope inside the step program's executions
    on one chip: {"steps", "step_module_s", "busy_s", scope: s, ...}, and
    ``holds_optimizer_s``: the time of operations counted under another
    scope that hold instructions of the optimizer's (XLA fuses the update
    into the products that make the weights' gradients)."""
    steps = step_modules(dev["modules"])
    inside, j = [], 0
    for op in dev["ops"]:
        while j < len(steps) and steps[j][1] <= op[0]:
            j += 1
        if j < len(steps) and steps[j][0] <= op[0]:
            inside.append(op)
    out, fused = dict.fromkeys(SCOPES, 0), 0
    for op, ns in zip(inside, exclusive(inside)):
        scope = scope_of(op[3])
        out[scope] += ns
        if scope != "optimizer" and "optimizer" in op[4].split("+"):
            fused += ns
    out = {k: v / 1e9 for k, v in out.items()}
    out.update(steps=len(steps), busy_s=sum(out.values()),
               step_module_s=sum(e - s for s, e, _ in steps) / 1e9,
               holds_optimizer_s=fused / 1e9)
    return out


def launch_leads_ms(host, modules):
    """For every device program whose launching span is known
    (``LAUNCHED_BY``), span start minus program start in ms, read across
    the two clocks: positive where the device's clock shows the program
    before the host's clock shows the span that launched it.  Programs and
    spans are paired in order; a kind whose counts do not divide is left
    out."""
    leads = []
    pairs = [([m for m in modules if m[2].startswith(prefix)], span_name)
             for prefix, span_name in LAUNCHED_BY]
    for programs, span_name in pairs + [(step_modules(modules), DISPATCH)]:
        spans = [h for h in host if h[2] == span_name]
        if not programs or not spans or len(programs) % len(spans):
            continue
        each = len(programs) // len(spans)
        leads.extend((spans[i // each][0] - p[0]) / 1e6
                     for i, p in enumerate(programs))
    return leads


def innermost(host, t):
    """Name of the span that started last among those holding ``t``."""
    inside = [h for h in host if h[0] <= t < h[1]]
    return max(inside, key=lambda h: h[0])[2] if inside else "between_spans"


def summary(loaded):
    """Everything ``report`` prints and the metric readers return."""
    trace = _trace()
    host = loaded["host"]
    parent, self_ns = nest(host)
    table = {}
    for i, h in enumerate(host):
        row = table.setdefault(h[2], {"ms": [], "self_ms": [], "parent":
                                      host[parent[i]][2]
                                      if parent[i] is not None else None})
        row["ms"].append((h[1] - h[0]) / 1e6)
        row["self_ms"].append(self_ns[i] / 1e6)
    spans = {name: {"count": len(r["ms"]),
                    "median_ms": statistics.median(r["ms"]),
                    "self_median_ms": statistics.median(r["self_ms"]),
                    "parent": r["parent"]} for name, r in table.items()}
    pre = [(h[0] - host[parent[i]][0]) / 1e6 for i, h in enumerate(host)
           if h[2] == DISPATCH and parent[i] is not None
           and host[parent[i]][2] == STEP]
    metrics = dict.fromkeys(METRICS)
    if pre:
        metrics["step_pre_dispatch_ms"] = statistics.median(pre)
    if DISPATCH in spans:
        metrics["step_dispatch_ms"] = spans[DISPATCH]["median_ms"]

    per_device = {n: scope_seconds(d)
                  for n, d in sorted(loaded["devices"].items())}
    scopes, fullest, skew, gaps = None, None, None, []
    if per_device:
        fullest = max(per_device, key=lambda n: per_device[n]["busy_s"])
        scopes = per_device[fullest]
        for scope in ("forward", "backward", "optimizer"):
            if scopes["steps"] and scopes[scope]:
                metrics["step_%s_ms" % scope] = \
                    1e3 * scopes[scope] / scopes["steps"]
        dev = loaded["devices"][fullest]
        leads = launch_leads_ms(host, dev["modules"])
        skew = max(0.0, max(leads)) if leads else None
        shift = int((skew or 0.0) * 1e6)
        busy = trace.union((s, e) for s, e, *_ in dev["ops"])
        idle = sorted(((b[0] - a[1], a[1]) for a, b in zip(busy, busy[1:])),
                      reverse=True)[:10]
        gaps = [[innermost(host, at + shift), g / 1e6] for g, at in idle]
    return {"spans": spans, "scopes": scopes, "fullest": fullest,
            "devices": per_device, "skew_floor_ms": skew,
            "idle_gaps_ms": gaps, "metrics": metrics}


@functools.lru_cache(maxsize=1)
def _summary_of(path):
    """One parse for the five readers of a run."""
    return summary(load(path))


def read_metric(name):
    """For ``metrics/<name>.py``: the metric from the run's trace, or
    None."""
    path = newest()
    return _summary_of(path)["metrics"][name] if path else None


# --------------------------------------------------------- command line
def report(path):
    s = summary(load(path))
    print("%-22s %6s %12s %12s  %s" % ("span", "count", "median ms",
                                       "self ms", "parent"))
    for name, r in sorted(s["spans"].items()):
        print("%-22s %6d %12.4f %12.4f  %s" % (
            name, r["count"], r["median_ms"], r["self_median_ms"],
            r["parent"] or "-"))
    if s["scopes"]:
        sc = s["scopes"]
        print("\nscopes on TPU:%s, %d executions of the step program "
              "(%.6f s; operations in them busy %.6f s)"
              % (s["fullest"], sc["steps"], sc["step_module_s"],
                 sc["busy_s"]))
        for scope in SCOPES:
            print("%-10s %10.4f ms a step %7.2f%%" % (
                scope, 1e3 * sc[scope] / max(1, sc["steps"]),
                100.0 * sc[scope] / sc["busy_s"] if sc["busy_s"] else 0.0))
        print("operations of another scope that hold optimizer instructions:"
              " %.4f ms a step" % (1e3 * sc["holds_optimizer_s"]
                                   / max(1, sc["steps"])))
    print("\nskew_floor_ms %s" % s["skew_floor_ms"])
    print("\nlongest device idle gaps, named by the innermost span at the "
          "gap's start after the shift:")
    for name, ms in s["idle_gaps_ms"]:
        print("%10.4f ms  %s" % (ms, name))
    print("\nmetrics %s" % s["metrics"])


def fixture(path, out, n_steps):
    """Trim a recorded trace to its first ``n_steps`` steps (the host's
    spans up to the start of the next ``mx.step``, each chip's events up to
    the end of its ``n_steps``-th step program: no time crosses the clocks)
    and keep the events beside what ``summary`` makes of them."""
    import gzip
    import json

    loaded = load(path)
    starts = [h[0] for h in loaded["host"] if h[2] == STEP]
    cut = starts[n_steps]
    loaded["host"] = [h for h in loaded["host"] if h[1] <= cut]
    for dev in loaded["devices"].values():
        cut = step_modules(dev["modules"])[n_steps - 1][1]
        for key in dev:
            dev[key] = [e for e in dev[key] if e[1] <= cut]
    with gzip.open(out, "wt") as f:
        json.dump({"source": os.path.basename(path), "steps": n_steps,
                   "loaded": loaded, "summary": summary(loaded)}, f)


if __name__ == "__main__":
    if len(sys.argv) < 3 or sys.argv[1] not in ("report", "fixture"):
        raise SystemExit(__doc__)
    if sys.argv[1] == "report":
        report(sys.argv[2])
    else:
        fixture(sys.argv[2], sys.argv[3], int(sys.argv[4]))
