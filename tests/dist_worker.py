"""Worker process for the REAL 2-process ``jax.distributed`` test.

Launched by ``scripts/launch.py --num-processes 2`` (the torchrun analog —
the capability the reference exercised with real multi-rank jobs,
``train.ipynb:640-653``). Each process owns 4 virtual CPU devices; the two
rendezvous over the DLTI_* env contract into one 8-device ZeRO-3 mesh and
train llama_tiny for a few steps on the SAME global batches a
single-process 8-device run consumes, so the test can assert loss
equality.

Data contract: every process builds the full deterministic global batch
and feeds its process-local row slice through
:func:`dlti_tpu.parallel.sharding.make_global_batch` (the production
multi-host assembly path). The committed host-shard *schedule*
(``HostShardedSchedule``) deliberately assigns different rows per host for
scalability, so this worker bypasses the dataset and slices the global
batch directly — the point here is numerical equivalence of the
distributed step, not the data schedule.

Usage: ``python tests/dist_worker.py OUT_JSON [n_steps] [strategy]``
(strategy: ``zero3`` (default), ``tp`` — ZeRO-3 fsdp=8, or fsdp=4 x
tensor=2 with the tensor axis spanning both processes, so TP's
row/column-parallel collectives really cross a process boundary — or
``pipe`` — data=4 x pipe=2 through the production Trainer: pipe stages
process-local (the ICI-like placement), batch rows sharded across the
hosts, the multi-host GPipe configuration r05 legalized.)
"""

import json
import os
import sys

_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.isdir(os.path.join(_repo_root, "dlti_tpu")):
    sys.path.insert(0, _repo_root)

N_LOCAL_DEVICES = 4  # per process; 2 processes -> 8-device global mesh


def main() -> None:
    out_path = sys.argv[1]
    n_steps = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    strategy = sys.argv[3] if len(sys.argv) > 3 else "zero3"

    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={N_LOCAL_DEVICES}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")

    from dlti_tpu.launcher import maybe_initialize_from_env

    assert maybe_initialize_from_env(), "launcher env missing"
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 2 * N_LOCAL_DEVICES, jax.device_count()

    import numpy as np

    from dlti_tpu.config import (
        Config, DataConfig, LoRAConfig, MODEL_PRESETS, OptimizerConfig,
        ParallelConfig, TrainConfig, ZeROStage,
    )
    from dlti_tpu.models import LlamaForCausalLM
    from dlti_tpu.parallel import (
        build_mesh, make_sharded_train_step, shard_train_state,
    )
    from dlti_tpu.parallel.sharding import make_global_batch
    from dlti_tpu.training import build_optimizer, create_train_state

    parallel = {
        "zero3": ParallelConfig(zero_stage=ZeROStage.ZERO3, fsdp=8),
        # fsdp=4 x tensor=2: with (fsdp, tensor)-major device order the
        # tensor pairs are process-local while the fsdp axis spans both
        # processes — a mixed TP x FSDP mesh whose cross-process
        # collectives (param all-gathers / grad reduce-scatters) compose
        # with TP-sharded kernels. The pure-fsdp mode already proves
        # cross-process collectives; this mode proves the composition.
        "tp": ParallelConfig(zero_stage=ZeROStage.ZERO3, fsdp=4, tensor=2),
        # data=4 x pipe=2: data-major order keeps each pipe pair
        # process-local (the natural deployment: GPipe over ICI within a
        # host, DP across hosts) while batch rows shard across the two
        # processes — the multi-host pipeline configuration.
        "pipe": ParallelConfig(data=4, pipe=2),
    }[strategy]
    cfg = Config(
        model=MODEL_PRESETS["llama_tiny"],
        lora=LoRAConfig(r=4, alpha=8, dropout=0.0),
        optimizer=OptimizerConfig(warmup_steps=2),
        parallel=parallel,
        data=DataConfig(max_seq_len=32),
        train=TrainConfig(micro_batch_size=8, grad_accum_steps=2),
    )
    rng = jax.random.PRNGKey(0)
    if strategy == "pipe":
        # The production Trainer path: init_state converts to the stacked
        # pipe layout + shards it; _build_step routes to the GPipe step.
        from dlti_tpu.training.trainer import Trainer

        trainer = Trainer(cfg)
        mesh = trainer.mesh
        state = trainer.init_state(rng)
        step = trainer._build_step(state)
    else:
        model = LlamaForCausalLM(cfg.model, cfg.lora)
        tx = build_optimizer(cfg.optimizer)
        state = create_train_state(rng, model, tx, (2, 32), lora_enabled=True)
        mesh = build_mesh(cfg.parallel)
        state = shard_train_state(state, cfg, mesh)
        step = make_sharded_train_step(model, state, cfg, mesh, accum_steps=2,
                                       donate=False)

    # Deterministic global batch, identical on every process AND in the
    # single-process reference run (tests/test_distributed.py).
    accum, bs, seq = 2, 8, 32
    np_rng = np.random.default_rng(7)
    global_ids = np_rng.integers(
        0, cfg.model.vocab_size, (accum, bs, seq)).astype(np.int32)
    rows_per_proc = bs // jax.process_count()
    lo = jax.process_index() * rows_per_proc
    local = {
        "input_ids": global_ids[:, lo:lo + rows_per_proc],
        "loss_mask": np.ones((accum, rows_per_proc, seq), np.int32),
    }
    batch = make_global_batch(local, cfg, mesh)

    losses = []
    for i in range(n_steps):
        state, metrics = step(state, batch, jax.random.fold_in(rng, i))
        losses.append(float(jax.device_get(metrics["loss"])))

    if jax.process_index() == 0:
        with open(out_path, "w") as f:
            json.dump({"losses": losses,
                       "process_count": jax.process_count(),
                       "device_count": jax.device_count()}, f)
    # All ranks participate in a final barrier-ish sync so rank 1 doesn't
    # exit while rank 0 still owns in-flight collectives.
    jax.block_until_ready(jax.tree_util.tree_leaves(state.params)[0])


if __name__ == "__main__":
    main()
