"""Device time under a named scope of the program, inside a compiled
program: what ``jax.named_scope("dlti_...")`` marks, read from the trace.

On the device's "XLA Ops" line an event is named after its HLO instruction
(``%fusion.12 = ...``). The scope a ``named_scope`` gave the operations it
covers is not in the event: it is a stat of the event's *metadata* (``tf_op``:
``jit(prefill)/.../dlti_mhc_map/dot_general:``), which
``jax.profiler.ProfileData`` does not hand out (an event's own stats there are
its offset, its duration and a time scale: my chip runs, PR 41). So this
module parses the ``.xplane.pb`` under ``ctx["profile_dir"]`` itself with the
protobuf classes that the installation's TensorFlow carries
(``tensorflow.tsl.profiler.protobuf.xplane_pb2``), in a process of its own,
and adds every operation event whose metadata names a scope that matches
``SCOPE`` to that scope, under the program execution it lies in. Time under a
scope is the *union* of its events' intervals: a loop's event and the events
of its body overlap. A fusion counts under the scope of the operation the
compiler named it after.

    {"programs": {"prefill": {"count": n, "scopes": {"dlti_mhc_map": s, ...}}}}

A trace whose operations carry no scope, or an installation without those
classes, gives nothing and every reader of it returns None: its metric is
then left out of the line.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import subprocess
import sys

import reduce_trace

RESULT_NAME = "scope_time.json"
SCOPE = re.compile(r"dlti_[a-z0-9_]+")
# The metadata stats that carry an operation's name with its scopes (others
# hold source paths, which name the package).
NAME_STATS = ("tf_op", "long_name")


def load_space(path: str):
    """The trace as an ``XSpace`` message (imports TensorFlow's copy of the
    profiler's protobuf classes: ten seconds, so never in the harness's own
    process)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _scopes_of(metadata, stat_names: dict) -> set:
    """The scopes one event metadata names: in its own name (a Mosaic
    kernel's) or in a ``NAME_STATS`` stat (a stat's text is its
    ``str_value`` or the name its ``ref_value`` points at)."""
    texts = [metadata.name]
    for stat in metadata.stats:
        if stat_names.get(stat.metadata_id) in NAME_STATS:
            texts.append(stat.str_value
                         or stat_names.get(stat.ref_value, ""))
    return set(SCOPE.findall(" ".join(texts)))


def scoped_events(space, rule: dict) -> dict:
    """``{program: {"count", "scopes": {scope: seconds}}}`` of the first
    device plane of an ``XSpace``."""
    for plane in space.planes:
        if not re.search(rule["device_planes"], plane.name):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        scopes_by_id = {k: _scopes_of(v, stat_names)
                        for k, v in plane.event_metadata.items()}
        modules, found = [], []       # (start, end, name); (start, end, scope)
        for line in plane.lines:
            t0 = line.timestamp_ns * 1000                # picoseconds
            if re.search(rule["program_lines"], line.name):
                modules += [(t0 + ev.offset_ps,
                             t0 + ev.offset_ps + ev.duration_ps,
                             plane.event_metadata[ev.metadata_id].name)
                            for ev in line.events]
            elif re.search(rule["op_lines"], line.name):
                for ev in line.events:
                    for scope in scopes_by_id.get(ev.metadata_id, ()):
                        found.append((t0 + ev.offset_ps, t0 + ev.offset_ps
                                      + ev.duration_ps, scope))
        modules.sort()
        starts = [m[0] for m in modules]
        out = {}
        for key, pattern in rule["programs"].items():
            mine = [m for m in modules if re.search(pattern, m[2])]
            by_scope: dict = {}
            for s, e, scope in found:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and modules[i][1] >= e \
                        and re.search(pattern, modules[i][2]):
                    by_scope.setdefault(scope, []).append((s, e))
            out[key] = {"count": len(mine), "scopes": {
                scope: reduce_trace.union_ns(spans) / 1e12
                for scope, spans in sorted(by_scope.items())}}
        return {"programs": out}
    return {"programs": {}}


def for_run(ctx: dict) -> dict | None:
    """The scoped times of this run's trace: computed once, in a process of
    its own, and kept beside the trace. None when there is no trace, or none
    that can be read."""
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


def scope_s_per_call(ctx: dict, program: str, prefix: str) -> float | None:
    """Seconds a call of ``program`` spends under scopes that start with
    ``prefix`` (summed over those scopes); None where the trace names none."""
    got = for_run(ctx)
    if not got or program not in got["programs"]:
        return None
    entry = got["programs"][program]
    times = [s for scope, s in entry["scopes"].items()
             if scope.startswith(prefix)]
    if not times or not entry["count"]:
        return None
    return sum(times) / entry["count"]


if __name__ == "__main__":
    # scope_time.py <profile dir> --out F   what for_run() runs
    # scope_time.py <profile dir>           look at a run by hand
    found = reduce_trace.find_xplane(sys.argv[1])
    result = scoped_events(load_space(found), reduce_trace.rules()) \
        if found else {"error": "no .xplane.pb under " + sys.argv[1]}
    if "--out" in sys.argv:
        with open(sys.argv[sys.argv.index("--out") + 1], "w") as f:
            json.dump(result, f)
    else:
        print(json.dumps(result, indent=1))
