"""What both kinds of cell share: the run directory, children, the device
gate, the compile-event log and the kept reference values.

This process never imports JAX: the chip belongs to the child that runs the
program's entry point, and the load generator lives here.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

import spec as spec_lib

PY = sys.executable
LIB = os.path.dirname(os.path.abspath(__file__))

# A compilation, or a program fetched from the persistent cache: either one
# inside the measured window means a shape was not warmed.
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class RunFailure(Exception):
    """The run cannot produce a result; the message is the one-line reason."""


class NoAccelerator(RunFailure):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class Run:
    """One run of one cell: where its files go and which children it has."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 rehearsal: bool, t_start: float, sweep: list = ()):
        self.sweep = list(sweep)
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.rehearsal, self.t_start = trace, rehearsal, t_start
        self.root = cell["root"]
        self.dir = os.path.join(self.root, ".bench_runs", cell["name"])
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        self.children: list = []
        self.notes: dict = {}  # goes out on the line before the result
        spec = dict(cell["cell"])
        if rehearsal:
            spec = overlay(spec, spec.get("rehearsal", {}))
        self.spec = spec
        config = dict(cell["config"])
        if rehearsal:
            # the tiny stand-in: the published keys, and what reaches the
            # program's ModelConfig verbatim, each with its own overrides
            for part in ("model", "program"):
                over = spec.get(part + "_overrides")
                if over:
                    config[part] = {**config.get(part, {}), **over}
        self.config = config
        self.reference_file = spec_lib.reference_file(config,
                                                      cell["bench_dir"])
        self.model_file = self.path("model.json")
        with open(self.model_file, "w") as f:
            json.dump(config, f)
        self.model_name = "bench_" + cell["config_name"]

    def path(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    # -- children --------------------------------------------------------
    def env(self) -> dict:
        env = {**os.environ, "PYTHONUNBUFFERED": "1"}
        env.pop("DLTI_MODEL_LAYERS", None)  # depth comes from the file
        # The compile cache lives inside the checkout, at a fixed path, and
        # keeps everything: a cache with a size limit drops the one entry
        # that matters (the 16-layer train step serialises to 213 MB; under
        # a 192 MiB limit every run compiled it again for 150 s - my chip
        # runs, PR 23). The program takes the directory from this variable.
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(self.root,
                                                        ".jax_cache")
        env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
        if self.rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
        return env

    def spawn(self, name: str, cmd: list):
        log_path = self.path(name + ".log")
        with open(log_path, "wb") as log:
            proc = subprocess.Popen(cmd, cwd=self.root, env=self.env(),
                                    stdout=log, stderr=subprocess.STDOUT,
                                    start_new_session=True)
        self.children.append(proc)
        return proc, log_path

    def spawn_entry(self, name: str, entry: str, argv: list):
        """The program's entry point under the shim (chip_child.py)."""
        self.events_path = self.path(name + ".compile_events.jsonl")
        self.facts_path = self.path(name + ".device.json")
        cmd = [PY, os.path.join(LIB, "chip_child.py"), "--entry", entry,
               "--model-file", self.model_file,
               "--model-name", self.model_name,
               "--events", self.events_path, "--facts", self.facts_path,
               "--", *argv]
        return self.spawn(name, cmd)

    def stop_all(self) -> None:
        for proc in self.children:
            stop(proc)

    # -- the device gate ---------------------------------------------------
    def wait_device(self, proc, log_path: str, deadline: float) -> dict:
        """What JAX in the child says the devices are; refuses anything but
        the chips the cell asks for (a CPU only under ``--rehearsal``)."""
        while not os.path.isfile(self.facts_path):
            if proc.poll() is not None:
                raise RunFailure(f"the child exited {proc.returncode} before "
                                 f"reaching JAX: {tail(log_path)}")
            if time.time() > deadline:
                raise RunFailure("the child did not reach JAX in time")
            time.sleep(0.05)
        facts = read_json(self.facts_path)
        want = "cpu" if self.rehearsal else "tpu"
        if facts["platform"] != want or facts["count"] < self.cell["chips"]:
            raise NoAccelerator(
                f"cell {self.cell['name']} needs {self.cell['chips']} x "
                f"{want}; JAX reports {facts['count']} x "
                f"{facts['platform']} ({facts['kind']})")
        return facts

    def device(self, facts: dict) -> dict:
        facts = {**facts, **read_json(self.facts_path)}
        peaks = [v for v in (facts.get("memory_peak_bytes") or {}).values()
                 if v]
        return {"platform": facts["platform"], "kind": facts["kind"],
                "count": facts["count"],
                "memory_peak_bytes": max(peaks) if peaks else None}

    # -- compilations ------------------------------------------------------
    def compilations_between(self, w0: float, w1: float) -> list:
        out = []
        for ev in read_jsonl(self.events_path):
            if ev["event"] in COMPILE_EVENTS and w0 <= ev["t"] < w1:
                out.append(ev)
        return out

    # -- reference values kept beside the compile cache ---------------------
    def cached_reference(self, inputs, compute) -> tuple:
        """(file, whether it was already there). ``compute(out_path)`` runs
        the reference process; what it writes is kept under a key of
        everything the *reference* depends on: the seeded ``inputs``, the
        whole configuration as run (its sizes, and the reference and the
        model constructor it names), the platform, and the bytes of that
        reference module and of check.py. Nothing the program under test
        computed may go into such a file: that is computed in every run (a
        kept verdict would vouch for code it never saw)."""
        h = hashlib.sha256()
        # the platform too: the seeded bf16 weights are not the same bits
        # on the CPU and on the TPU (my runs, PR 23: reference loss 11.15829
        # and 11.15882 from one key)
        for part in (inputs, self.config, "cpu" if self.rehearsal else "tpu"):
            h.update(json.dumps(part, sort_keys=True).encode())
        for path in (self.reference_file, os.path.join(LIB, "check.py")):
            with open(path, "rb") as f:
                h.update(f.read())
        cache_dir = os.path.join(self.root, ".bench_cache", "checks")
        os.makedirs(cache_dir, exist_ok=True)
        path = os.path.join(cache_dir, h.hexdigest()[:32] + ".json")
        hit = os.path.isfile(path)
        if not hit:
            compute(path)
        return path, hit

    def judged_by(self) -> dict:
        """Which reference and which model constructor the configuration
        led the check to, for the notes line."""
        return {"reference": os.path.relpath(self.reference_file, self.root),
                "program_model": ":".join(
                    spec_lib.program_model(self.config))}

    def run_check(self, what: str, extra: list, out: str,
                  timeout_s: float) -> None:
        proc, log_path = self.spawn(
            "check_" + what,
            [PY, os.path.join(LIB, "check.py"), what, "--model-file",
             self.model_file, *extra, "--out", out,
             "--platform", "cpu" if self.rehearsal else "tpu"])
        try:
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            stop(proc)
            raise RunFailure(f"check.py {what} did not finish in "
                             f"{timeout_s:.0f} s")
        if rc == 3:
            raise NoAccelerator(f"check.py {what} found no "
                                f"accelerator: {tail(log_path, 2)}")
        if rc != 0 or not os.path.isfile(out):
            raise RunFailure(f"check.py {what} exited {rc}: "
                             f"{tail(log_path)}")


def overlay(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on it, object by object. An object in
    ``over`` that says ``"replace": true`` takes the place of the base's
    whole (a set of shapes cannot be merged with another)."""
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and v.get("replace"):
            out[k] = {a: b for a, b in v.items() if a != "replace"}
        elif isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = overlay(out[k], v)
        else:
            out[k] = v
    return out


def flags(args: dict) -> list:
    """``{"--flag": "value", "--switch": null}`` -> argv."""
    out = []
    for k, v in args.items():
        if v is False:
            continue
        out.append(k)
        if v is not None and v is not True:
            out.append(str(v))
    return out


def stop(proc, grace_s: float = 20.0) -> None:
    if proc.poll() is not None:
        return
    for sig, wait_s in ((signal.SIGTERM, grace_s), (signal.SIGKILL, 10.0)):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(timeout=wait_s)
            break
        except subprocess.TimeoutExpired:
            continue


def read_text(path: str) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def read_jsonl(path: str) -> list:
    rows = []
    for line in read_text(path).splitlines():
        try:
            rows.append(json.loads(line))
        except ValueError:
            pass  # a line still being written
    return rows


def tail(path: str, n: int = 12) -> str:
    return " | ".join(read_text(path).strip().splitlines()[-n:])[-1500:]
