"""Every device idle gap of a traced run, put down to what the host was doing.

With its tracer enabled the program enters a profiler annotation for every
span (``dlti_tpu/telemetry/tracer.py``), so the xplane holds them on the
host plane beside the device's operations, on one clock. This reduction
takes the first device's idle gaps as ``reduce_trace.reduce`` defines them
(between the merged "XLA Ops" intervals) and gives every instant of a gap
to the innermost span open on the stepper thread at that instant (serving:
the thread that runs ``server/step``; training: the trainer's loop thread);
what no span covers is ``unattributed``. It also sums device time by kernel
name, checks that the two clocks are one (the end of a decode or train-step
execution on the device falls inside the host's wait for it) and, from the
tracer's own ring export, gives CPU over wall time of the stepper's spans.

Which planes, lines, span names and kernel names, is data:
``span_rules.json`` (written down after looking at one traced run of each
cell by hand: PERF.md, "Reading a trace"); devices, operation and program
lines and the programs' names come from ``trace_rules.json``. Files under
``benchmark/rules/`` add kernels, span groups, clock checks and programs
to the two (``spec.load_rules``; benchmark/rules/README.md). Two stages,
like ``reduce_trace``: ``load`` reads an ``.xplane.pb`` into plain lists
(host lines cut to the program's spans), ``attribute`` works on those, so
the arithmetic is checked on small traces written by hand.

    trace = {"planes": [{"name": str, "lines": [{"name": str,
             "events": [[name, start_ns, duration_ns], ...]}]}]}

A trace of a program that annotates no spans (the parent of the PR that
added this file) attributes nothing: ``spans`` is None and every reader of
it returns None.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import subprocess
import sys

import reduce_trace
import spec as spec_lib

RESULT_NAME = "idle_attribution.json"


def rules(bench_dir: str = spec_lib.BENCH_DIR) -> dict:
    rule = spec_lib.load_rules("span_rules.json", spec_lib.SPAN_SECTIONS,
                               bench_dir)
    unplaced = sorted(set(rule["kernels"]) - set(rule["kernels_per"]))
    if unplaced:
        raise spec_lib.SpecError(
            f"the rules give kernel {', '.join(unplaced)} no program to "
            f"count its steps by (kernels_per)")
    return rule


def load(path: str, rule: dict, device_rule: dict) -> dict:
    """Read an xplane file (imports jax's profiler reader): the devices'
    operation and program lines whole, the host's thread lines cut to the
    program's spans."""
    from jax.profiler import ProfileData

    prefixes = tuple(rule["span_prefixes"])
    device_lines = f'{device_rule["op_lines"]}|{device_rule["program_lines"]}'
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        if re.search(device_rule["device_planes"], plane.name):
            for line in plane.lines:
                if re.search(device_lines, line.name):
                    lines.append({"name": line.name, "events": [
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]})
        elif re.search(rule["host_planes"], plane.name):
            for line in plane.lines:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                          for ev in line.events if ev.name.startswith(prefixes)]
                if events:
                    lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def idle_gaps(ops: list) -> list:
    """``[(start_ns, end_ns)]`` between the merged intervals of ``ops``."""
    gaps, end = [], None
    for s, e in sorted((s, s + d) for _, s, d in ops if d > 0):
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None or e > end else end
    return gaps


def innermost_segments(spans: list) -> list:
    """``[(start, end, path)]``, sorted and disjoint, from the nested spans
    ``[(start, end, name)]`` of one thread: each piece of time with the
    names of the spans that cover it, outermost first. A child that ends
    after its parent (clock granularity) is cut to it."""
    out, stack, t = [], [], 0

    def close():
        nonlocal t
        end = stack[-1][0]
        if end > t:
            out.append((t, end, tuple(n for _, n in stack)))
            t = end
        stack.pop()

    for start, end, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= start:
            close()
        if stack:
            if start > t:
                out.append((t, start, tuple(n for _, n in stack)))
            end = min(end, stack[-1][0])
        t = max(t, start) if stack else start
        stack.append((end, name))
    while stack:
        close()
    return out


def _stepper_line(trace: dict, rule: dict) -> tuple:
    """``(events, mark)`` of the host thread that carries the most stepper
    marks, and which mark that was; ``(None, None)`` without one. A span on
    any other thread (a handler's, the prefetcher's) does not count."""
    best, most, which = None, 0, None
    for plane in trace["planes"]:
        if not re.search(rule["host_planes"], plane["name"]):
            continue
        for line in plane["lines"]:
            for mark in rule["stepper_marks"]:
                n = sum(1 for ev in line["events"] if ev[0] == mark)
                if n > most:
                    best, most, which = line["events"], n, mark
    return best, which


def _split_gaps(gaps: list, segments: list) -> tuple:
    """Idle nanoseconds by span path, and per gap by innermost span."""
    starts = [s for s, _, _ in segments]
    by_path: dict = {}
    per_gap = []
    for a, b in gaps:
        inner: dict = {}
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            s, e, path = segments[i]
            part = min(e, b) - max(s, a)
            if part > 0:
                by_path[path] = by_path.get(path, 0) + part
                inner[path[-1]] = inner.get(path[-1], 0) + part
            i += 1
        per_gap.append((a, b, inner))
    return by_path, per_gap


def _group_of(path: tuple, groups: dict) -> str | None:
    """The first group (in the rules' order) that claims a span path."""
    for key, g in groups.items():
        if set(path) & set(g.get("under", ())):
            return key
        prefix = g.get("innermost_prefix")
        if prefix and path[-1].startswith(prefix):
            return key
    return None


def _clock_check(execs: list, line: list, names: dict) -> dict | None:
    """Are the two clocks one? The device finishes a program while the host
    waits for its results, so the end of an execution lies inside a wait
    span of the stepper thread (a host clock that runs behind the device's
    breaks this first); and where the host launches a round only after the
    one before has ended (``launch``), no launch begins while the program
    runs (a host clock that runs ahead breaks that first). Judged are the
    executions that end between the first wait's start and the last wait's
    end (the others were waited for outside the capture).
    ``inside_share``: ended in the wait for this program;
    ``inside_any_wait_share``: in that or in another span in which the host
    blocks on the device behind it (the wait for a prefill, which the
    device runs after it; admission, which reads a new slot's sampling key
    back and so waits for the running decode: my chip runs, PR 25)."""
    own = sorted((s, s + d) for n, s, d in line if n in names["wait"])
    if not own:
        return None
    judged = [(s, e) for s, e in execs
              if own[0][0] <= e <= max(b for _, b in own)]
    out = {}
    for key in ("wait", "any_wait"):
        waits = sorted((s, s + d) for n, s, d in line if n in names[key])
        starts = [s for s, _ in waits]
        out[key] = 0
        for _, e in judged:
            i = bisect.bisect_right(starts, e) - 1
            out[key] += i >= 0 and waits[i][0] <= e <= waits[i][1]
    launches = sorted(s for n, s, _ in line if n in names.get("launch", ()))
    overlapped = sum(
        bisect.bisect_left(launches, e) > bisect.bisect_right(launches, s)
        for s, e in judged)
    n = len(judged)
    return {"judged": n,
            "inside_share": out["wait"] / n if n else None,
            "inside_any_wait_share": out["any_wait"] / n if n else None,
            "launch_inside_execution_share":
                overlapped / n if n and launches else None}


def attribute(trace: dict, rule: dict | None = None,
              device_rule: dict | None = None) -> dict | None:
    """The reduction. Times in seconds unless the key says otherwise; None
    when the trace holds no device plane."""
    rule = rule or rules()
    device_rule = device_rule or reduce_trace.rules()
    devices = [p for p in trace["planes"]
               if re.search(device_rule["device_planes"], p["name"])]
    if not devices:
        return None
    device = devices[0]  # as reduce_trace's breakdown: the first chip
    ops = reduce_trace._events(device, device_rule["op_lines"])
    modules = reduce_trace._events(device, device_rule["program_lines"])
    gaps = idle_gaps(ops)
    idle_ns = sum(b - a for a, b in gaps)
    executions = {
        key: sorted((s, s + d) for n, s, d in modules
                    if re.search(pattern, n))
        for key, pattern in device_rule["programs"].items()}
    out = {
        "device": device["name"], "idle_s": idle_ns / 1e9,
        "gaps": len(gaps),
        "executions": {k: len(v) for k, v in executions.items()},
        "kernels": {}, "spans": None,
    }
    for key, pattern in rule["kernels"].items():
        # the instruction's own name: the text after " = " names its
        # operands too, and a kernel's consumers would match
        durs = [d for n, _, d in ops
                if re.search(pattern, n.split(" = ", 1)[0])]
        steps = len(executions.get(rule["kernels_per"][key], ()))
        if durs and steps:
            out["kernels"][key] = {
                "events": len(durs), "total_s": sum(durs) / 1e9,
                "per": rule["kernels_per"][key], "steps": steps,
                "ms_per_step": sum(durs) / 1e6 / steps,
                "events_per_step": len(durs) / steps}
    line, mark = _stepper_line(trace, rule)
    if line is None:
        return out
    program = rule["step_program"][mark]
    prefixes = tuple(rule["span_prefixes"])
    spans = [(s, s + d, n) for n, s, d in line if n.startswith(prefixes)]
    by_path, per_gap = _split_gaps(gaps, innermost_segments(spans))
    attributed = sum(by_path.values())
    by_span: dict = {}
    groups = {key: 0 for key in rule["groups"]}
    other = 0
    for path, ns in by_path.items():
        by_span[path[-1]] = by_span.get(path[-1], 0) + ns
        group = _group_of(path, rule["groups"])
        if group is None:
            other += ns
        else:
            groups[group] += ns
    steps = len(executions[program])
    longest = []
    for a, b, inner in sorted(per_gap, key=lambda g: g[0] - g[1])[:10]:
        name, ns = max(inner.items(), key=lambda kv: kv[1],
                       default=("unattributed", 0))
        longest.append({"at_s": a / 1e9, "length_s": (b - a) / 1e9,
                        "span": name, "span_share": ns / (b - a)})
    out["spans"] = {
        "stepper_mark": mark, "step_program": program,
        "attributed_share": attributed / idle_ns if idle_ns else None,
        "idle_by_span_s": {k: v / 1e9 for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "idle_by_path_s": {">".join(k): v / 1e9 for k, v in sorted(
            by_path.items(), key=lambda kv: -kv[1])},
        "unattributed_s": (idle_ns - attributed) / 1e9,
        "groups_s": {k: v / 1e9 for k, v in groups.items()},
        "other_spans_s": other / 1e9,
        "steps": steps,
        "idle_ms_per_step": idle_ns / 1e6 / steps if steps else None,
        "groups_ms_per_step": {k: v / 1e6 / steps for k, v in groups.items()}
        if steps else None,
        "longest_gaps": longest,
        "clock_check": _clock_check(executions[program], line,
                                    rule["clock_check"][program]),
    }
    return out


def ring_cpu(export: dict, rule: dict | None = None) -> dict | None:
    """CPU over wall time of the ``server/step`` spans that lie inside the
    captured window, from the tracer's ring export (``ts``/``dur`` in
    microseconds of ``time.monotonic()``, ``args.cpu_us`` the CPU time of
    the span's thread). Wall less CPU is time the stepper thread was off
    the CPU: waiting for the device, for a lock, for the interpreter.
    ``by_span`` gives the same two sums for every span name of that thread,
    so that a phase that waits can be told from one that computes."""
    rule = rule or rules()
    events = export.get("traceEvents", [])
    first, last = rule["ring"]["window"]
    starts = [e["ts"] for e in events if e["name"] == first]
    stops = [e["ts"] for e in events if e["name"] == last]
    if not starts or not stops or max(stops) <= starts[-1]:
        return None
    lo, hi = starts[-1], max(stops)
    of = rule["ring"]["cpu_share_of"]
    prefixes = tuple(rule["span_prefixes"])
    tids = {e["tid"] for e in events if e["name"] == of}
    wall = cpu = 0.0
    by_span: dict = {}
    for e in events:
        if (e.get("ph") != "X" or e["tid"] not in tids
                or not e["name"].startswith(prefixes)
                or "cpu_us" not in e.get("args", {})
                or e["ts"] < lo or e["ts"] + e["dur"] > hi):
            continue
        row = by_span.setdefault(e["name"], {"count": 0, "wall_s": 0.0,
                                             "cpu_s": 0.0})
        row["count"] += 1
        row["wall_s"] += e["dur"] / 1e6
        row["cpu_s"] += e["args"]["cpu_us"] / 1e6
        if e["name"] == of:
            wall += e["dur"] / 1e6
            cpu += e["args"]["cpu_us"] / 1e6
    if wall <= 0:
        return None
    return {"window_s": (hi - lo) / 1e6, "wall_s": wall, "cpu_s": cpu,
            "share": cpu / wall, "by_span": by_span}


def reduce_profile_dir(profile_dir: str) -> dict | None:
    """Attribute the newest trace under ``profile_dir``; with the ring
    export beside it (in the directory above), the stepper's CPU share."""
    path = reduce_trace.find_xplane(profile_dir)
    if path is None:
        return None
    rule, device_rule = rules(), reduce_trace.rules()
    out = attribute(load(path, rule, device_rule), rule, device_rule)
    if out is None:
        return None
    out["stepper_cpu"] = None
    exports = sorted(glob.glob(os.path.join(
        os.path.dirname(os.path.abspath(profile_dir)),
        rule["ring"]["export"])))
    if exports:
        with open(exports[-1]) as f:
            out["stepper_cpu"] = ring_cpu(json.load(f), rule)
    return out


# -- what the readers in benchmark/layer_metrics/ call -----------------------

def for_run(ctx: dict) -> dict | None:
    """The attribution of this run's trace: computed once, in a process of
    its own (reading a trace imports jax), and kept beside the trace so
    that every reader shares it. None when there is no trace, or none that
    can be read: a reader then returns None and its metric is left out."""
    profile_dir = ctx.get("profile_dir")
    if not profile_dir or not os.path.isdir(profile_dir):
        return None
    out = os.path.join(profile_dir, RESULT_NAME)
    if not os.path.isfile(out):
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), profile_dir,
                 "--out", out], env={**os.environ, "JAX_PLATFORMS": "cpu"},
                capture_output=True, text=True, timeout=600)
            error = proc.stderr[-800:] if proc.returncode else None
        except subprocess.TimeoutExpired:
            error = "no end within 600 s"
        if error is not None or not os.path.isfile(out):
            with open(out, "w") as f:
                json.dump({"error": error}, f)
    with open(out) as f:
        got = json.load(f)
    return got if got and "error" not in got else None


def attributed_share(ctx: dict, mark: str) -> float | None:
    """Percent of the device's idle time that a span of the stepper thread
    covers, for a run whose stepper carries ``mark``."""
    got = for_run(ctx)
    if not got or not got["spans"] or got["spans"]["stepper_mark"] != mark \
            or got["spans"]["attributed_share"] is None:
        return None
    return 100.0 * got["spans"]["attributed_share"]


def idle_ms_per_step(ctx: dict, group: str) -> float | None:
    got = for_run(ctx)
    if not got or not got["spans"] or not got["spans"]["groups_ms_per_step"]:
        return None
    return got["spans"]["groups_ms_per_step"].get(group)


def kernel_ms_per_step(ctx: dict, kernel: str) -> float | None:
    got = for_run(ctx)
    if not got or kernel not in got["kernels"]:
        return None
    return got["kernels"][kernel]["ms_per_step"]


def startup_seconds(ctx: dict, names: list) -> float | None:
    """Sum of the named ``/metrics`` series as the window opened (start-up
    is over by then); None for a program without the start-up series (the
    ready gauge came with them). A counter the registry has not rendered
    yet (nothing counted) is 0."""
    before = ctx.get("metrics_before") or {}
    if "dlti_startup_ready_seconds" not in before:
        return None
    return sum(before.get(name, 0.0) for name in names)


if __name__ == "__main__":
    # attribute_idle.py <profile dir> --out F   what for_run() runs
    # attribute_idle.py <profile dir>           look at a run by hand
    target = sys.argv[1]
    result = reduce_profile_dir(target)
    if "--out" in sys.argv:
        with open(sys.argv[sys.argv.index("--out") + 1], "w") as f:
            json.dump(result, f)
        sys.exit(0)
    print(json.dumps(result, indent=1))
