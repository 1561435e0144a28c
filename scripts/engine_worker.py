#!/usr/bin/env python
"""Fleet engine worker entrypoint — one engine process behind the wire
protocol (``dlti_tpu.serving.worker``), spawned and supervised by
``dlti_tpu.serving.fleet.FleetSupervisor`` (``scripts/serve.py
--fleet-workers N``).

The worker builds its model the same way ``serve.py`` does — a
``--random-init`` preset initializes from ``jax.random.PRNGKey(0)``, so
every worker process (and any in-process replica built from the same
preset) holds byte-identical weights; that, plus the engine's
batch-composition-independent sampling, is what makes fleet outputs
byte-identical to the single-process engine.

All build parameters arrive as one JSON spec file (``--spec``) written by
the supervisor; after the engine is up and the socket is bound, the
chosen port is published via ``--port-file`` (durable write) for the
supervisor to pick up.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.isdir(os.path.join(_repo_root, "dlti_tpu")):
    sys.path.insert(0, _repo_root)
del _repo_root

from dlti_tpu.utils.platform import enable_compilation_cache

enable_compilation_cache()


def parse_args():
    p = argparse.ArgumentParser(description="fleet engine worker")
    p.add_argument("--spec", required=True,
                   help="JSON build spec written by the fleet supervisor")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="listen port (0 = ephemeral, published via "
                        "--port-file)")
    p.add_argument("--port-file", default="",
                   help="publish the bound port here once ready to serve")
    p.add_argument("--worker-id", type=int, default=0)
    p.add_argument("--generation", type=int, default=0,
                   help="respawn generation (tags flight dumps)")
    return p.parse_args()


def build_engine(spec: dict):
    """Model + engine construction, mirroring ``serve.py``. Returns
    (engine, rebuild_fn) where rebuild_fn(host_params) makes a fresh
    engine for rolling weight reloads."""
    import jax
    import jax.numpy as jnp

    from dlti_tpu.serving import EngineConfig, InferenceEngine

    if spec.get("matmul_precision"):
        # Byte-identity across processes requires the same matmul
        # precision the supervisor-side reference engine runs under
        # (tests force "highest"; the env half of the platform dance is
        # inherited, this config knob is not).
        jax.config.update("jax_default_matmul_precision",
                          spec["matmul_precision"])

    if spec.get("model_dir"):
        from dlti_tpu.checkpoint import load_exported_model

        params, cfg = load_exported_model(spec["model_dir"])
        model_cfg = cfg.model
        lora_cfg = cfg.lora if cfg.lora.enabled else None
    else:
        from dlti_tpu.config import resolve_model
        from dlti_tpu.models import build_model

        model_cfg = resolve_model(spec["model_preset"])
        lora_cfg = None
        model = build_model(model_cfg, None)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]

    eng_kwargs = dict(spec["engine"])
    for key in ("prefill_buckets", "adapter_targets"):
        if key in eng_kwargs and eng_kwargs[key] is not None:
            eng_kwargs[key] = tuple(eng_kwargs[key])
    ec = EngineConfig(**eng_kwargs)

    def rebuild(host_params):
        return InferenceEngine(model_cfg, host_params, ec, lora_cfg,
                               donate_params=True)

    engine = InferenceEngine(model_cfg, params, ec, lora_cfg,
                             donate_params=True)
    return engine, rebuild


def main() -> None:
    args = parse_args()
    with open(args.spec, encoding="utf-8") as f:
        spec = json.load(f)
    # Per-worker identity for flight-dump tagging (flightrecorder labels
    # dumps -g{generation}-r{process_id}); the supervisor sets these in
    # the child env, the flags win if both are present.
    os.environ["DLTI_PROCESS_ID"] = str(args.worker_id)
    os.environ["DLTI_GENERATION"] = str(args.generation)

    for name, adir in (spec.get("adapters") or {}).items():
        from dlti_tpu.serving.adapters import register_adapter

        register_adapter(name, adir)

    # Span federation: the worker keeps a local span ring and ships its
    # tail to the supervisor in FT_STEP/FT_HEALTH replies; the label
    # names this process's row in the merged Perfetto timeline.
    if spec.get("trace", True):
        from dlti_tpu.telemetry import configure_tracer

        tracer = configure_tracer(enabled=True)
        tracer.process_label = f"worker{args.worker_id} gen{args.generation}"

    engine, rebuild = build_engine(spec)
    if spec.get("slow_log_k"):
        engine.telemetry.critical_path.slow.k = max(
            1, int(spec["slow_log_k"]))
    if spec.get("warmup", True):
        engine.warmup_decode_ladder()

    # Per-worker metrics registry: the health-frame snapshot the
    # supervisor federates into the gateway-level /metrics.
    import types

    from dlti_tpu.serving.server import build_registry
    from dlti_tpu.serving.worker import EngineWorker

    registry = build_registry(types.SimpleNamespace(engine=engine))

    if spec.get("flight_dir"):
        from dlti_tpu.telemetry import install_recorder
        from dlti_tpu.telemetry.flightrecorder import FlightRecorder

        # Per-process dump namespace: the supervisor and every worker
        # write to their own subdir; postmortem.py --all walks one level
        # of subdirs and merges them into a single incident timeline.
        recorder = FlightRecorder(os.path.join(
            spec["flight_dir"], f"worker{args.worker_id}"))
        recorder.add_metrics_source(registry.stats_dict)
        recorder.note(role="fleet-worker", worker=args.worker_id,
                      generation=args.generation)
        install_recorder(recorder)

    def _rebuild_warm(tree):
        eng = rebuild(tree)
        if spec.get("warmup", True):
            eng.warmup_decode_ladder()
        return eng

    worker = EngineWorker(engine, host=args.host, port=args.port,
                          worker_id=args.worker_id, registry=registry,
                          reload_fn=_rebuild_warm)

    if args.port_file:
        from dlti_tpu.utils.durable_io import write_json_atomic

        write_json_atomic(args.port_file,
                          {"port": worker.port, "pid": os.getpid(),
                           "worker_id": args.worker_id,
                           "generation": args.generation},
                          path_class="fleet_runtime")
    print(f"engine worker {args.worker_id} (gen {args.generation}) "
          f"serving on {worker.host}:{worker.port}", flush=True)
    try:
        worker.serve_forever()
    finally:
        worker.close()


if __name__ == "__main__":
    main()
