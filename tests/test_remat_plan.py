"""The trainer's kept-block rule (``dlti_tpu/training/remat_plan.py``): the
arithmetic as pure functions, the trainer's own jitted step at every count
against full remat, the plan's line, row, ledger entry and gauge, and a
compile refused for memory."""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from dlti_tpu.config import (
    MODEL_PRESETS, CheckpointConfig, Config, DataConfig, LoRAConfig,
    ParallelConfig, TelemetryConfig, TrainConfig,
)
from dlti_tpu.telemetry import memledger
from dlti_tpu.training import remat_plan
from dlti_tpu.training.remat_plan import RematPlan
from dlti_tpu.training.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = 4
MODEL = dataclasses.replace(MODEL_PRESETS["llama_tiny"], num_layers=LAYERS,
                            remat=True)
GIB = 1 << 30


def config(**over) -> Config:
    fields = dict(
        model=MODEL, lora=LoRAConfig(enabled=True, r=4, alpha=8, dropout=0.0),
        data=DataConfig(max_seq_len=32),
        train=TrainConfig(micro_batch_size=2, grad_accum_steps=1,
                          num_epochs=1, max_steps=3, logging_steps=1),
        checkpoint=CheckpointConfig(save_strategy="no"))
    fields.update(over)
    return Config(**fields)


def batch(cfg: Config, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    shape = (1, cfg.train.micro_batch_size, cfg.data.max_seq_len)
    return {"input_ids": rng.integers(0, cfg.model.vocab_size, shape,
                                      dtype=np.int32),
            "loss_mask": np.ones(shape, np.int32)}


# -- (a) the trainer's own step, whatever the count ---------------------------

def two_steps(keep: int):
    """(the two steps' metrics, the state after them) of the trainer's
    jitted step with ``keep`` blocks kept."""
    cfg = config()
    trainer = Trainer(cfg)
    trainer.adopt_remat_plan(RematPlan(keep, LAYERS))
    assert trainer.model.cfg.remat_keep_blocks == keep
    state = trainer.init_state()
    step = trainer._build_step(state)
    key = jax.random.PRNGKey(3)
    seen = []
    for i in range(2):
        state, metrics = step(state, batch(cfg, i), key)
        seen.append(jax.device_get(metrics))
    return seen, jax.device_get((state.params, state.opt_state))


@pytest.fixture(scope="module")
def full_remat():
    return two_steps(0)


@pytest.mark.parametrize("keep", [0, 1, LAYERS // 2, LAYERS])
def test_the_trainers_step_computes_what_full_remat_computes(full_remat,
                                                             keep):
    """Loss, gradient norm, and after two AdamW steps every LoRA factor and
    both of its moments (the first moment after step one is a tenth of the
    gradient): what is kept changes what the backward recomputes and
    nothing it computes."""
    seen, after = two_steps(keep)
    for got, want in zip(seen, full_remat[0]):
        assert float(got["loss"]) == pytest.approx(float(want["loss"]),
                                                   rel=1e-6)
        assert float(got["grad_norm"]) == pytest.approx(
            float(want["grad_norm"]), rel=1e-5)
    moved = 0
    for got, want in zip(jax.tree_util.tree_leaves(after),
                         jax.tree_util.tree_leaves(full_remat[1])):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
        moved += 1
    assert moved > 8 * LAYERS  # the factors and their moments were there


# -- (b) the rule as a pure function ---------------------------------------------

def test_no_room_keeps_none_and_room_for_all_keeps_all():
    assert remat_plan.most_blocks_that_fit(16, 10 * GIB, GIB, 10 * GIB) == 0
    assert remat_plan.most_blocks_that_fit(16, 10 * GIB, GIB, 4 * GIB) == 0
    assert remat_plan.most_blocks_that_fit(16, GIB, GIB, 100 * GIB) == 16
    assert remat_plan.most_blocks_that_fit(16, GIB, 0, 100 * GIB) == 0


def test_the_count_is_monotone_in_the_limit_and_never_over_it_less_margin():
    base, block = 9 * GIB + 12345, 800 * (1 << 20) + 7
    before = 0
    for limit in range(8 * GIB, 40 * GIB, GIB // 3):
        k = remat_plan.most_blocks_that_fit(16, base, block, limit)
        assert before <= k <= 16
        before = k
        under = limit - int(remat_plan.MARGIN_FRACTION * limit)
        assert k == 0 or base + k * block <= under
        assert k == 16 or base + (k + 1) * block > under
    assert before == 16


def test_the_closed_form_at_the_benchmarks_shape():
    """mistral_7b's widths, 4 x 2,048 tokens, bfloat16, LoRA on q/k/v/o:
    the bytes the issue lists (input, normed input, q, k, v, attention
    output and log-sum-exp, second residual, gate, up)."""
    model = dataclasses.replace(MODEL_PRESETS["mistral_7b"], num_layers=16)
    got = remat_plan.kept_block_bytes(8192, model, LoRAConfig())
    mib = 1 << 20
    assert got == (64 + 64 + 64 + 16 + 16 + 64 + 64 + 224 + 224 + 1) * mib
    # a LoRA factor on the MLP reads the MLP's inputs too
    wide = LoRAConfig(target_modules=("q_proj", "gate_proj", "down_proj"))
    assert remat_plan.kept_block_bytes(8192, model, wide) \
        == got + (64 + 224) * mib
    # a tensor axis shards the head and FFN widths, not the residual stream
    assert remat_plan.kept_block_bytes(8192, model, LoRAConfig(), 4) \
        == (64 + 64 + 64) * mib + (64 + 16 + 16 + 64 + 224 + 224 + 1) \
        * mib // 4


def test_a_device_under_fsdp_counts_its_share_and_keeps_more():
    limit = 2 << 20
    one = remat_plan.plan(config(), 1 << 20, limit)
    four = remat_plan.plan(
        config(parallel=ParallelConfig(fsdp=4, zero_stage=3),
               train=dataclasses.replace(config().train,
                                         micro_batch_size=8)),
        (1 << 20) // 4, limit)
    # (8 rows over 4 devices: the tokens a device are those of 2 rows)
    assert four.block_bytes == one.block_bytes
    assert four.base_bytes < one.base_bytes
    assert four.keep_blocks > one.keep_blocks
    assert one.planned_bytes <= limit - int(
        remat_plan.MARGIN_FRACTION * limit)


@pytest.mark.parametrize("over,why", [
    (dict(model=dataclasses.replace(MODEL, remat_policy="dots_saveable")),
     "stated"),
    (dict(model=dataclasses.replace(MODEL, remat_stride=2)), "stated"),
    (dict(model=dataclasses.replace(MODEL, remat=False)), "stated"),
    (dict(model=dataclasses.replace(MODEL, remat_keep_blocks=0)), "stated"),
    (dict(parallel=ParallelConfig(pipe=2)), "pipe > 1"),
    (dict(parallel=ParallelConfig(sequence=2)), "sequence > 1"),
    (dict(model=dataclasses.replace(MODEL, num_experts=4)), "expert"),
])
def test_where_the_rule_stands_aside_it_keeps_today_and_says_why(over, why):
    plan = remat_plan.plan(config(**over), 1 << 20, 1 << 40)
    assert plan.keep_blocks == 0 and why in plan.why_not
    assert plan.line().startswith(
        "remat: 0 of 4 blocks keep their activations as the configuration "
        "has it, unplanned (") and why in plan.line()


def test_without_a_limit_the_rule_keeps_none():
    plan = remat_plan.plan(config(), 1 << 20, 0)
    assert plan.keep_blocks == 0 and "no memory limit" in plan.why_not


# -- the plan in the trainer's run: line, first row, ledger, gauge ------------------

def run(tmp_path, cfg: Config):
    """Three steps of ``Trainer(cfg).train``; (the trainer, the step log's
    step rows)."""
    log = str(tmp_path / "steps.jsonl")
    cfg = cfg.replace(telemetry=dataclasses.replace(
        cfg.telemetry, step_log_path=log))
    trainer = Trainer(cfg)
    trainer.train(batches_per_epoch=[batch(cfg, i) for i in range(3)])
    with open(log) as fh:
        rows = [json.loads(line) for line in fh]
    return trainer, [r for r in rows if r.get("type") == "step"]


def room_for(keep: int) -> int:
    """A budget under which the rule keeps ``keep`` of the tiny model's
    blocks and no more."""
    trainer = Trainer(config(
        telemetry=TelemetryConfig(hbm_budget_bytes=1 << 40)))
    all_kept = trainer.plan_remat(trainer.init_state())
    assert all_kept.keep_blocks == LAYERS
    under = all_kept.base_bytes + keep * all_kept.block_bytes + 1
    return int(under / (1 - remat_plan.MARGIN_FRACTION)) + 2


def test_the_run_states_its_plan(tmp_path, engine_log):
    budget = room_for(2)
    trainer, rows = run(tmp_path, config(
        telemetry=TelemetryConfig(hbm_budget_bytes=budget)))
    plan = trainer.remat_plan
    assert plan.keep_blocks == 2 and plan.limit_bytes == budget
    assert trainer.model.cfg.remat_keep_blocks == 2
    assert trainer.cfg.model.remat_keep_blocks is None
    lines = [r.getMessage() for r in engine_log.records
             if r.getMessage().startswith("remat:")]
    assert lines == [plan.line()]
    assert "2 of 4 blocks keep their activations; planned" in lines[0]
    assert {k: rows[0][k] for k in plan.scalars()} == plan.scalars()
    assert rows[0]["remat_planned_bytes"] <= budget
    assert "remat_kept_blocks" not in rows[1]
    assert memledger.remat_kept_blocks_gauge.value == 2
    assert trainer._memledger.to_dict()["remat_plan"] == plan.scalars()
    assert len(rows) == 3 and all(np.isfinite(r["loss"]) for r in rows)


def test_a_stated_count_is_left_alone(tmp_path, engine_log):
    trainer, rows = run(tmp_path, config(
        model=dataclasses.replace(MODEL, remat_stride=2),
        telemetry=TelemetryConfig(hbm_budget_bytes=1 << 40)))
    assert trainer.remat_plan.keep_blocks == 0
    assert trainer.model.cfg.remat_keep_blocks == 0
    assert any("remat policy or stride is stated" in r.getMessage()
               for r in engine_log.records)
    assert rows[0]["remat_kept_blocks"] == 0


def train_cli_config(monkeypatch, *flags):
    """``scripts/train.py``'s configuration for a command line."""
    import importlib.util
    import sys

    spec = importlib.util.spec_from_file_location(
        "train_cli", os.path.join(REPO, "scripts", "train.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    monkeypatch.setattr(sys, "argv", [
        "train.py", "--model", "llama_debug", "--num-devices", "1", *flags])
    return cli.build_config(cli.parse_args())


@pytest.mark.parametrize("flags,planned", [
    ((), True),
    (("--preset", "zero2"), True),
    (("--remat-stride", "2"), False),
    (("--remat-stride", "1"), False),
    (("--remat-policy", "nothing_saveable"), False),
    (("--remat-policy", "save_attn_out"), False),
    (("--remat-policy", "none"), False),
])
def test_a_stated_remat_flag_leaves_the_rule_unused(monkeypatch, flags,
                                                    planned):
    """With neither flag the configuration says nothing and the rule
    decides; either flag, whatever its value, states a count of 0 beside
    it, and the policy or stride is what the user typed."""
    cfg = train_cli_config(monkeypatch, *flags)
    plan = remat_plan.plan(cfg, 1 << 20, 1 << 40)
    if planned:
        assert cfg.model.remat_keep_blocks is None and not plan.why_not
        assert plan.keep_blocks == cfg.model.num_layers
    else:
        assert cfg.model.remat_keep_blocks == 0
        assert plan.keep_blocks == 0 and "stated" in plan.why_not
        if "--remat-stride" in flags:
            assert cfg.model.remat_stride == int(flags[1])


def test_the_planner_prints_the_plan_the_trainer_makes():
    """``scripts/memory_plan.py plan_training``: the same function, so the
    same numbers, from the same state bytes and limit."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "memory_plan", os.path.join(REPO, "scripts", "memory_plan.py"))
    planner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(planner)
    cfg = config()
    budget = 1_800_000  # room for two of the four blocks
    printed = planner.plan_training(
        MODEL, trainable_params=planner.lora_trainable_params(MODEL, r=4),
        budget_bytes=budget, micro_batch_size=2, seq_len=32, lora_r=4)
    held = sum(v for k, v in printed["owners"].items()
               if k != "activations")
    plan = remat_plan.plan(cfg, held, budget)
    assert 0 < plan.keep_blocks < LAYERS
    assert printed["remat_plan"] == {**plan.scalars(), "line": plan.line()}
    assert printed["owners"]["activations"] == plan.planned_bytes - held
    assert printed["total_bytes"] == plan.planned_bytes and printed["fits"]
    assert plan.line() in planner.render(printed)
    # the trainer counts the state it placed: the same bytes to 3 %
    trainer = Trainer(config(
        telemetry=TelemetryConfig(hbm_budget_bytes=budget)))
    own = trainer.plan_remat(trainer.init_state())
    assert own.base_bytes == pytest.approx(plan.base_bytes, rel=0.03)
    assert own.keep_blocks == plan.keep_blocks
    # without rows and a length the plan is what it always was
    assert "remat_plan" not in planner.plan_training(MODEL,
                                                     budget_bytes=budget)


# -- (c) a compile refused for memory ------------------------------------------------

def test_a_step_refused_its_memory_steps_down_and_trains(tmp_path,
                                                         monkeypatch,
                                                         engine_log):
    """The refusal injected at the program's call, where jit compiles: the
    programs that keep 3 and 2 blocks are refused, the one that keeps 1
    trains."""
    built = []
    real = Trainer._build_step

    def build(self, state):
        keep = self.model.cfg.remat_keep_blocks
        built.append(keep)
        step = real(self, state)
        if keep < 2:
            return step

        def refused(*_a):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
                "out of memory in memory space hbm. Used 16.10G of 15.75G "
                "hbm.")
        return refused

    monkeypatch.setattr(Trainer, "_build_step", build)
    trainer, rows = run(tmp_path, config(
        telemetry=TelemetryConfig(hbm_budget_bytes=room_for(3))))
    assert built == [3, 2, 1]
    assert trainer.remat_plan.keep_blocks == 1
    warned = [r.getMessage() for r in engine_log.records
              if "was refused its memory" in r.getMessage()]
    assert len(warned) == 2 and "keeps 3 blocks" in warned[0] \
        and "Used 16.10G of 15.75G" in warned[0] \
        and warned[1].endswith("keeping 1")
    assert rows[0]["remat_kept_blocks"] == 1
    assert len(rows) == 3 and all(np.isfinite(r["loss"]) for r in rows)


def test_another_fault_is_not_a_refusal(tmp_path, monkeypatch):
    def build(self, state):
        def broken(*_a):
            raise ValueError("not about memory")
        return broken

    monkeypatch.setattr(Trainer, "_build_step", build)
    with pytest.raises(ValueError, match="not about memory"):
        run(tmp_path, config(
            telemetry=TelemetryConfig(hbm_budget_bytes=room_for(3))))


# -- the arithmetic is held to the chip's reading ------------------------------------

def drill_rows() -> list:
    path = os.path.join(REPO, "results", "remat_plan_v5e.jsonl")
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def test_the_closed_form_is_held_to_the_compiled_steps_on_the_chip():
    """``results/remat_plan_v5e.jsonl`` (benchmarks_dev/remat_plan_drill.py
    on one v5e): a kept block's bytes within 3 % of what the compiler's
    temp bytes rise by a block, and the plan for every count never under
    what the compiled step takes (arguments + temporaries; outputs alias
    the donated state) by more than 2 % of it."""
    rows = {r["keep_blocks"]: r for r in drill_rows()
            if "temp_size_in_bytes" in r}
    assert 0 in rows and len(rows) >= 4
    model = dataclasses.replace(MODEL_PRESETS["mistral_7b"], num_layers=16)
    block = remat_plan.kept_block_bytes(8192, model, LoRAConfig())
    assert rows[0]["closed_form_block_bytes"] == block
    for k, row in rows.items():
        took = row["argument_size_in_bytes"] + row["temp_size_in_bytes"]
        planned = rows[0]["closed_form_base_bytes"] + k * block
        assert planned >= 0.98 * took, (k, planned, took)
        if 0 < k <= 5:
            rise = (row["temp_size_in_bytes"]
                    - rows[0]["temp_size_in_bytes"]) / k
            assert rise == pytest.approx(block, rel=0.03), (k, rise)
