"""From a profiler trace to numbers: busy and idle time of the device, time
per compiled program, and the breakdown the result line carries.

Two stages, so that the arithmetic can be checked on a small recorded trace
without the profiler: ``load`` reads an ``.xplane.pb`` with nothing but
``jax.profiler.ProfileData`` into plain lists; ``reduce`` works on those.
Which planes are devices, which lines hold operations and which hold whole
programs, and how the programs are named, is data: ``trace_rules.json``
(written down after looking at one trace of this program by hand — see
PERF.md, "Reading a trace"); files under ``benchmark/rules/`` add programs
to it (``spec.load_rules``).

    trace = {"planes": [{"name": str, "lines": [{"name": str,
             "events": [[name, start_ns, duration_ns], ...]}]}]}
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics

import spec as spec_lib


def rules(bench_dir: str = spec_lib.BENCH_DIR) -> dict:
    return spec_lib.load_rules("trace_rules.json", spec_lib.TRACE_SECTIONS,
                               bench_dir)


def find_xplane(profile_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        profile_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def load(path: str, keep_planes: str | None = None) -> dict:
    """Read an xplane file. Imports jax (only its profiler reader)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if keep_planes and not re.search(keep_planes, plane.name):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union_ns(intervals: list) -> int:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _events(plane: dict, line_pattern: str) -> list:
    out = []
    for line in plane["lines"]:
        if re.search(line_pattern, line["name"]):
            out.extend(line["events"])
    return out


def reduce(trace: dict, rule: dict | None = None) -> dict:
    """Per device and averaged over devices. Times in seconds.

    ``busy_s``: union of the intervals in which an operation ran on the
    device; ``window_s``: first start to last end of anything any device
    did (the traced window as the devices saw it); ``idle_share`` = 1 -
    busy / window. ``programs``: for each name pattern in the rules, the
    executions of whole compiled programs that match it (count, total and
    median seconds, averaged over devices).
    """
    rule = rule or rules()
    devices = [p for p in trace["planes"]
               if re.search(rule["device_planes"], p["name"])]
    if not devices:
        return {"devices": 0}
    per_device = []
    lo = hi = None
    for plane in devices:
        ops = _events(plane, rule["op_lines"])
        spans = [(s, s + d) for _, s, d in ops if d > 0]
        for s, e in spans:
            lo = s if lo is None or s < lo else lo
            hi = e if hi is None or e > hi else hi
        by_op: dict = {}
        for n, _, d in ops:
            by_op[op_family(n)] = by_op.get(op_family(n), 0) + d
        programs = {}
        modules = _events(plane, rule["program_lines"])
        for key, pattern in rule["programs"].items():
            durs = sorted(d for n, _, d in modules if re.search(pattern, n))
            programs[key] = {
                "count": len(durs), "total_s": sum(durs) / 1e9,
                "median_s": statistics.median(durs) / 1e9 if durs else None}
        gaps = []
        merged = sorted(spans)
        end = None
        for s, e in merged:
            if end is not None and s > end:
                gaps.append((s - end, end))
            end = e if end is None or e > end else end
        per_device.append({
            "name": plane["name"], "busy_ns": union_ns(spans),
            "by_op": by_op, "programs": programs,
            "gaps": sorted(gaps, reverse=True)[:10],
            "module_events": sorted((s, s + d, n) for n, s, d in modules),
        })
    n = len(per_device)
    window_ns = (hi - lo) if lo is not None else 0
    busy_s = sum(d["busy_ns"] for d in per_device) / n / 1e9
    out = {
        "devices": n, "window_s": window_ns / 1e9, "busy_s": busy_s,
        "idle_share": (1 - busy_s / (window_ns / 1e9)) if window_ns else None,
        "programs": {},
    }
    for key in rule["programs"]:
        counts = [d["programs"][key]["count"] for d in per_device]
        meds = [d["programs"][key]["median_s"] for d in per_device
                if d["programs"][key]["median_s"] is not None]
        out["programs"][key] = {
            "count": sum(counts) / n,
            "total_s": sum(d["programs"][key]["total_s"]
                           for d in per_device) / n,
            "median_s": statistics.median(meds) if meds else None}
    # The breakdown, from the first device: operations by total time, and
    # the longest gaps named by the programs on either side (what the host
    # was doing in a gap is not in this trace yet: the tracing issue).
    first = per_device[0]
    top = sorted(first["by_op"].items(), key=lambda kv: -kv[1])[:10]
    out["device_ops"] = [[name[:120], ns / 1e9] for name, ns in top]
    idle = []
    for length, at in first["gaps"]:
        before = [m for m in first["module_events"] if m[1] <= at + 1000]
        after = [m for m in first["module_events"] if m[0] >= at + length - 1000]
        label = (f"after {_short(before[-1][2]) if before else 'start'}, "
                 f"before {_short(after[0][2]) if after else 'end'}")
        idle.append([label[:120], length / 1e9])
    out["idle_gaps"] = idle
    return out


def op_family(name: str) -> str:
    """``%fusion.123 = (...) fusion(...)`` -> ``fusion``: operations are
    summed by the name the compiler gave them, less its serial number, so
    that the sixteen layers' copies of one fusion count as one entry."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def _short(name: str) -> str:
    return re.sub(r"\(.*", "", name)[:40]


def reduce_profile_dir(profile_dir: str) -> dict | None:
    """Reduce the newest trace under ``profile_dir``; None if there is none
    or it holds no device plane."""
    path = find_xplane(profile_dir)
    if path is None:
        return None
    rule = rules()
    reduced = reduce(load(path, rule["keep_planes"]), rule)
    return reduced if reduced.get("devices") else None


if __name__ == "__main__":
    # reduce_trace.py <profile dir> --out F   what the harness runs (in a
    #                                         process of its own: reading a
    #                                         trace imports jax)
    # reduce_trace.py <dir or file>           look at a trace by hand
    import sys

    target = sys.argv[1]
    if "--out" in sys.argv:
        with open(sys.argv[sys.argv.index("--out") + 1], "w") as f:
            json.dump(reduce_profile_dir(target), f)
        sys.exit(0)
    path = target if target.endswith(".pb") else find_xplane(target)
    trace = load(path)
    for plane in trace["planes"]:
        print(plane["name"], [(ln["name"], len(ln["events"]))
                              for ln in plane["lines"]])
    print(json.dumps(reduce(trace), indent=1)[:6000])
