"""What the TPU bring-up (PR 21) made true, pinned on CPU.

One process per chip: importing the package must not take it. One compile
cache location. No made-up device peaks. One kernel-selection rule.
``chip_smoke.py`` fails without an accelerator, never touches jax itself,
and its CPU rehearsal runs the whole train -> export -> serve path.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _py(code, env_extra=None, cwd=REPO, timeout=180, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


# ----------------------------------------------------------------------
# One process per chip
# ----------------------------------------------------------------------

def test_importing_every_module_leaves_the_backend_uninitialised():
    """A parent that imports dlti_tpu (any module of it) and then starts a
    child that needs the chip must not already hold the chip."""
    proc = _py(
        "import importlib, pkgutil, sys\n"
        "import dlti_tpu\n"
        "names = ['dlti_tpu'] + [m.name for m in pkgutil.walk_packages(\n"
        "    dlti_tpu.__path__, 'dlti_tpu.')]\n"
        "for n in names:\n"
        "    importlib.import_module(n)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), (\n"
        "    'a module-scope statement initialised a backend')\n"
        "from dlti_tpu.utils.logging import get_logger, is_main_process\n"
        "get_logger('x').info('a log line')\n"
        "assert is_main_process()\n"
        "assert not xla_bridge.backends_are_initialized(), 'logging did'\n"
        "print('MODULES', len(names))\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert int(proc.stdout.split("MODULES")[1]) > 60


def test_multiprocess_entry_points_refuse_on_tpu(monkeypatch):
    import jax

    from dlti_tpu.utils.platform import refuse_multiprocess_on_tpu

    refuse_multiprocess_on_tpu("x")  # the CPU backend: several may share
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit) as e:
        refuse_multiprocess_on_tpu("scripts/serve.py --fleet-workers 2")
    msg = str(e.value)
    assert "\n" not in msg and "not supported on TPU" in msg
    assert "--fleet-workers 2" in msg and "--replicas" in msg


# ----------------------------------------------------------------------
# One compile cache location
# ----------------------------------------------------------------------

_CACHE_PROBE = (
    "import jax\n"
    "from dlti_tpu.utils.platform import (\n"
    "    compilation_cache_dir, enable_compilation_cache)\n"
    "enable_compilation_cache()\n"
    "print(repr(compilation_cache_dir()), '|',\n"
    "      jax.config.jax_compilation_cache_dir)\n")


def test_cache_dir_from_env_is_left_to_jax():
    proc = _py(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": "/x/y"})
    assert proc.returncode == 0, proc.stderr[-1000:]
    ours, jaxs = (s.strip() for s in proc.stdout.strip().split("|"))
    assert ours == "None"       # the code resolves no directory...
    assert jaxs == "/x/y"       # ...and JAX's own reading of the env stands


def test_cache_dir_default_is_the_checkout_from_any_process(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    env = {"PYTHONPATH": REPO}
    a = _py(_CACHE_PROBE, env, drop=("JAX_COMPILATION_CACHE_DIR",))
    b = _py(_CACHE_PROBE, env, cwd=str(tmp_path),
            drop=("JAX_COMPILATION_CACHE_DIR",))
    for proc in (a, b):
        assert proc.returncode == 0, proc.stderr[-1000:]
        assert proc.stdout.strip() == f"{want!r} | {want}"


# ----------------------------------------------------------------------
# No made-up device peaks
# ----------------------------------------------------------------------

def _fake_devices(monkeypatch, platform, kind):
    import jax

    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])


def test_peak_lookup_is_exact_or_an_error(monkeypatch):
    from dlti_tpu.utils import metrics

    _fake_devices(monkeypatch, "tpu", "TPU v5 lite")  # what a v5e reports
    assert metrics.chip_peak_flops() == 197e12
    _fake_devices(monkeypatch, "tpu", "TPU v9 imaginary")
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        metrics.chip_peak_flops()
    assert "cpu" not in metrics.CHIP_PEAK_FLOPS


def test_cpu_run_has_no_mfu():
    from dlti_tpu.utils import metrics

    assert metrics.chip_peak_flops() is None
    assert metrics.compute_mfu(1000.0, 7_000_000_000, None) is None
    assert metrics.MetricsRecord(
        "e", 1, 0, "baseline", 0.1, 1.0, 1.0, 2.0).mfu_percent is None
    assert metrics.device_peak_memory()[1] == "host_rss"


def test_accelerator_without_memory_stats_raises(monkeypatch):
    from dlti_tpu.utils import metrics

    _fake_devices(monkeypatch, "tpu", "TPU v5 lite")
    monkeypatch.setattr(metrics, "device_memory_stats", lambda: {})
    with pytest.raises(RuntimeError, match="host RSS"):
        metrics.device_peak_memory()


# ----------------------------------------------------------------------
# One kernel-selection rule; the depth cut
# ----------------------------------------------------------------------

def test_kernel_selection_rule(monkeypatch):
    import jax

    from dlti_tpu.ops.attention import resolve_flash, resolve_paged_decode

    aligned = dict(seq_q=512, seq_kv=512, head_dim=128)
    # CPU: auto stays on XLA; a forced kernel is interpreted.
    assert resolve_flash("auto", **aligned)[0] == "xla"
    assert resolve_flash("flash", **aligned)[0] == "pallas-interpret"
    assert resolve_paged_decode("auto", tp_sharded=False)[0] == "xla"
    assert resolve_paged_decode(
        "kernel", tp_sharded=False)[0] == "pallas-interpret"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_flash("auto", **aligned) == (
        "pallas", "auto on TPU, tile-aligned")
    path, why = resolve_flash("auto", seq_q=500, seq_kv=500, head_dim=128)
    assert path == "xla" and "unaligned" in why
    assert resolve_flash("reference", **aligned)[0] == "xla"
    assert resolve_paged_decode("auto", tp_sharded=False)[0] == "pallas"
    path, why = resolve_paged_decode("kernel", tp_sharded=True)
    assert path == "xla" and "TP-sharded" in why

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="Pallas kernels target the TPU"):
        resolve_flash("flash", **aligned)


def test_depth_cut_is_an_env_var_and_cuts_nothing_else(monkeypatch):
    import dataclasses

    from dlti_tpu.config import MODEL_PRESETS, preset, resolve_model

    full = MODEL_PRESETS["mistral_7b"]
    assert resolve_model("mistral_7b") is full
    monkeypatch.setenv("DLTI_MODEL_LAYERS", "8")
    cut = resolve_model("mistral_7b")
    assert cut == dataclasses.replace(full, num_layers=8)
    assert preset("baseline", model="mistral_7b").model == cut
    for bad in ("0", "33", "x", "-1"):
        monkeypatch.setenv("DLTI_MODEL_LAYERS", bad)
        with pytest.raises(ValueError, match="whole layers"):
            resolve_model("mistral_7b")
    with pytest.raises(ValueError, match="unknown model"):
        resolve_model("mistral_7b:layers=8")


def _fsdp_cfg(tmp_path, **train):
    from dlti_tpu.config import (
        CheckpointConfig, Config, DataConfig, MODEL_PRESETS, ParallelConfig,
        TrainConfig, ZeROStage,
    )

    return Config(
        model=MODEL_PRESETS["llama_debug"],  # remat on, like the 7B presets
        parallel=ParallelConfig(zero_stage=ZeROStage.ZERO3, fsdp=4),
        data=DataConfig(max_seq_len=128, tokenizer="byte"),
        checkpoint=CheckpointConfig(save_strategy="no",
                                    output_dir=str(tmp_path)),
        train=TrainConfig(micro_batch_size=4, grad_accum_steps=1, **train))


def test_sharded_trainer_initialises_sharded(tmp_path):
    """On four chips the whole initialised tree sat on device 0 next to
    device 0's shard (init-then-shard; Flax keeps an eager init scope alive
    behind nn.remat). Under a mesh the state is now the output of one
    compiled initialiser: every leaf has its resting sharding, equals what
    a single device initialises from the same seed, and no whole
    single-device copy of a sharded leaf exists afterwards."""
    import dataclasses

    import jax

    from dlti_tpu.config import ParallelConfig
    from dlti_tpu.parallel.sharding import state_shardings
    from dlti_tpu.training import Trainer

    cfg = _fsdp_cfg(tmp_path)
    before = {id(a) for a in jax.live_arrays()}
    trainer = Trainer(cfg)
    state = trainer.init_state()
    want = state_shardings(state, cfg, trainer.mesh)
    sharded_kinds = set()
    for leaf, sh in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(want)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
        if not leaf.is_fully_replicated:
            sharded_kinds.add((leaf.shape, leaf.dtype))
    assert sharded_kinds  # the embedding and the head, at least
    leftovers = [(a.shape, a.dtype) for a in jax.live_arrays()
                 if id(a) not in before
                 and len(a.sharding.device_set) == 1
                 and (a.shape, a.dtype) in sharded_kinds]
    assert not leftovers, leftovers
    single = Trainer(cfg.replace(parallel=ParallelConfig())).init_state()
    for got, ref in zip(jax.tree_util.tree_leaves(state.params),
                        jax.tree_util.tree_leaves(single.params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_sharded_trainer_leaves_the_callers_base_params_alone(tmp_path):
    """Base weights the caller handed in are placed leaf by leaf onto their
    shards and stay the caller's: still alive afterwards (device arrays
    included), and a second init_state grafts them again. With an int8
    frozen base the quantized leaves come out sharded too."""
    import jax
    import jax.numpy as jnp

    from dlti_tpu.training import Trainer

    cfg = _fsdp_cfg(tmp_path)
    donor = Trainer(cfg).init_state(jax.random.PRNGKey(7)).params
    embed = np.asarray(donor["model"]["embed_tokens"])
    norm = jnp.asarray(np.asarray(donor["model"]["final_norm"]["scale"]) + 1)
    base = {"model": {"embed_tokens": embed, "final_norm": {"scale": norm}}}
    trainer = Trainer(cfg, base_params=base)
    for _ in range(2):
        params = trainer.init_state().params
        got = params["model"]["embed_tokens"]
        assert not got.is_fully_replicated
        np.testing.assert_array_equal(np.asarray(got), embed)
        np.testing.assert_array_equal(
            np.asarray(params["model"]["final_norm"]["scale"]),
            np.asarray(norm))
    assert not norm.is_deleted()

    q = Trainer(_fsdp_cfg(tmp_path, quantize_frozen_base="int8"),
                base_params=base).init_state().params
    node = q["model"]["embed_tokens"]
    assert node["q"].dtype == jnp.int8 and not node["q"].is_fully_replicated


def test_pipe_refuses_the_tpu_flash_kernel_with_sharded_axes(monkeypatch):
    """Inside a pipeline stage the kernel cannot be wrapped per shard and
    GSPMD cannot partition a Mosaic call: refused at construction on a TPU,
    allowed where the kernel is interpreted or absent."""
    import dataclasses

    import jax

    from dlti_tpu.config import MODEL_PRESETS, preset
    from dlti_tpu.training.trainer import _validate_pipeline_config

    cfg = preset("baseline", model=MODEL_PRESETS["llama_tiny"])
    cfg = cfg.replace(
        parallel=dataclasses.replace(cfg.parallel, pipe=2, data=2),
        data=dataclasses.replace(cfg.data, max_seq_len=128),
        model=dataclasses.replace(cfg.model, attention_impl="flash"))
    _validate_pipeline_config(cfg)  # CPU: interpreted, partitions fine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="Pallas flash kernel on a TPU"):
        _validate_pipeline_config(cfg)
    _validate_pipeline_config(cfg.replace(parallel=dataclasses.replace(
        cfg.parallel, data=1)))  # pipe alone: nothing to partition
    _validate_pipeline_config(cfg.replace(model=dataclasses.replace(
        cfg.model, attention_impl="reference")))


# ----------------------------------------------------------------------
# chip_smoke.py
# ----------------------------------------------------------------------

_RUN_SMOKE = (
    "import runpy, sys\n"
    "sys.argv = [{path!r}] + {argv!r}\n"
    "code = 0\n"
    "try:\n"
    "    runpy.run_path({path!r}, run_name='__main__')\n"
    "except SystemExit as e:\n"
    "    code = e.code or 0\n"
    "heavy = sorted(m for m in sys.modules if m == 'jax'\n"
    "               or m.startswith(('jax.', 'dlti_tpu')))\n"
    "print('PARENT_LOADED', heavy)\n"
    "sys.exit(code)\n")


def test_chip_smoke_without_accelerator_fails_and_never_loads_jax():
    proc = _py(_RUN_SMOKE.format(path=SMOKE, argv=[]),
               {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 3, (proc.stdout, proc.stderr[-1500:])
    out = [l for l in proc.stdout.splitlines() if l.strip()]
    # No result line — only this test's own marker, and it shows the
    # parent process imported neither jax nor the package.
    assert out == ["PARENT_LOADED []"], out
    err = [l for l in proc.stderr.splitlines() if l.strip()]
    assert len(err) == 1 and "no accelerator" in err[0], err
    assert "'cpu'" in err[0]


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    lone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = subprocess.run([sys.executable, lone], cwd=tmp_path,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "not in a checkout" in proc.stderr


def test_chip_smoke_cpu_rehearsal_runs_the_whole_path():
    """train.py -> checkpoint + export -> serve.py -> concurrent
    completions -> SIGTERM, through chip_smoke.py's own checks, at
    llama_tiny size. Says platform: cpu, so it can never pass for a chip
    run."""
    argv = ["--cpu-rehearsal", "--layers", "1",  # the depth cut, too
            "--legs", "train,serve,serve_full_depth"]
    proc = _py(_RUN_SMOKE.format(path=SMOKE, argv=argv),
               {"JAX_PLATFORMS": "cpu"}, timeout=600, drop=("XLA_FLAGS",))
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert lines[-1] == "PARENT_LOADED []"
    # The result is the script's last line: these keys and no others.
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert json.loads(lines[-2]) == {"ok": True, "device": device}
    out = json.loads(lines[-3])  # the summary, one line before it
    assert out["ok"] is True
    assert out["device"] == device
    assert out["model"] == "llama_tiny" and out["depth"] == 1
    assert set(out["legs"]) == {"train", "serve", "serve_full_depth"}
    assert out["legs"]["serve_full_depth"]["depth"] == 2  # as published
    assert all(leg["ok"] for leg in out["legs"].values())
    assert out["legs"]["train"]["committed_checkpoints"]
    assert out["legs"]["serve"]["decode_steps"] > 0
    assert out["attention"]["train"] == "xla"  # kernels are for the TPU
    assert out["block_allocator"] in ("native", "python")
