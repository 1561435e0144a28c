"""The program against the plain reference at tiny widths on the CPU: loss,
per-token log-probs and gradients through the program's model, loss
function and training attention path, for a Mistral-like block (sliding window shorter than the
row, GQA group 4, packed documents) and a Qwen2-like one (q/k/v bias, GQA
group 7). Logits through prefill and paged decode are held against the same
reference by the serving cells' rehearsals in test_bench_run.py."""

import json
import os

import pytest

import bench_paths  # noqa: F401

import check

MISTRAL_LIKE = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "max_position_embeddings": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "sliding_window": 48,
    "torch_dtype": "float32"}
QWEN2_LIKE = {
    "vocab_size": 512, "hidden_size": 112, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 7,
    "num_key_value_heads": 1, "head_dim": 16, "max_position_embeddings": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-6, "attention_bias": True,
    "use_sliding_window": False, "sliding_window": 4096,
    "torch_dtype": "float32"}


class Args:
    pass


# float32 on both sides: what is left is summation order
TIGHT = {"loss_abs": 1e-5, "token_logprob_rms": 1e-5, "grad_norm_rel": 1e-4,
         "grad_cosine_min": 0.9999}


def both_sides(tmp_path, model, tolerance=TIGHT):
    """The reference's side written to a file, then the program's side
    held against it: the two processes of a training cell, in this one."""
    args = Args()
    args.model_file = str(tmp_path / "model.json")
    args.spec = str(tmp_path / "spec.json")
    args.out = args.reference = str(tmp_path / "kept.json")
    json.dump({"model": model, "program": {"remat": False}},
              open(args.model_file, "w"))
    json.dump({"lora_r": 4, "seed": 5, "rows": 2, "seq_len": 128,
               "doc_median": 40, "tolerance": tolerance},
              open(args.spec, "w"))
    kept = check.train_reference(args)
    json.dump(kept, open(args.reference, "w"))
    return args, kept


@pytest.mark.parametrize("model", [MISTRAL_LIKE, QWEN2_LIKE],
                         ids=["mistral_like", "qwen2_like"])
def test_loss_log_probs_and_gradients_agree_with_the_reference(tmp_path,
                                                               model):
    args, kept = both_sides(tmp_path, model)
    got = check.train_program(args)
    assert got["ok"], got
    assert got["reference_grad_norm"] > 0
    assert got["token_logprob_max_diff"] < 1e-4
    assert {k.split("/")[-1] for k in got["by_group"]} == \
        {"lora_a", "lora_b"}
    assert any(k.startswith("k_proj") for k in got["by_group"])


def test_the_kept_file_holds_nothing_the_program_computed(tmp_path):
    _, kept = both_sides(tmp_path, MISTRAL_LIKE)
    assert set(kept) == {"reference_loss", "reference_grad_norm", "arrays",
                         "device"}
    import numpy as np

    arrays = np.load(tmp_path / kept["arrays"])
    assert arrays["token_logprobs"].shape == (2, 127)
    assert all(k == "token_logprobs" or k.startswith("grad:")
               for k in arrays.files)
    assert any(k.endswith("lora_b") for k in arrays.files)


def test_a_kept_reference_does_not_vouch_for_a_changed_program(tmp_path,
                                                                monkeypatch):
    """The program's side is computed each time: a model whose logits are
    off by 1 % is refused against the file an earlier, sound run left."""
    args, _ = both_sides(tmp_path, MISTRAL_LIKE)
    assert check.train_program(args)["ok"]
    from dlti_tpu.models import LlamaForCausalLM

    sound = LlamaForCausalLM.apply

    def off(self, *a, **kw):
        logits, cache = sound(self, *a, **kw)
        return logits * 1.01, cache

    monkeypatch.setattr(LlamaForCausalLM, "apply", off)
    got = check.train_program(args)
    assert not got["ok"]
    assert got["token_logprob_rms_diff"] > TIGHT["token_logprob_rms"]


def _verdict(**changed):
    """compare_train on hand-made numbers: one LoRA pair, three tokens."""
    import numpy as np

    ref_grads = {"q_proj": {"lora_a": np.array([3.0, 0.0]),
                            "lora_b": np.array([0.0, 4.0])}}
    arrays = {"token_logprobs": np.array([[-1.0, -2.0, -3.0]]),
              "grad:q_proj/lora_a": ref_grads["q_proj"]["lora_a"],
              "grad:q_proj/lora_b": ref_grads["q_proj"]["lora_b"]}
    mine = {"loss": 2.0, "picked": np.array([[-1.0, -2.0, -3.0]]),
            "grads": ref_grads, **changed}
    tol = {"loss_abs": 0.01, "token_logprob_rms": 0.01,
           "grad_norm_rel": 0.1, "grad_cosine_min": 0.5}
    ref = {"reference_loss": 2.0, "device": {}}
    mask = np.array([[1, 1, 1, 0]])  # the last target is padding
    return check.compare_train(mine["loss"], mine["picked"], mine["grads"],
                               ref, arrays, mask, tol)


def test_compare_train_accepts_equal_sides():
    got = _verdict()
    assert got["ok"] and got["grad_cosine"] == pytest.approx(1.0)
    assert got["grad_norm_ratio"] == pytest.approx(1.0)
    assert got["token_logprob_rms_diff"] == 0.0


@pytest.mark.parametrize("changed,field", [
    ({"loss": 2.02}, "loss_abs_diff"),
    ({"picked": [[-1.0, -2.05, -3.0]]}, "token_logprob_rms_diff"),
    ({"grads": {"q_proj": {"lora_a": [6.0, 0.0], "lora_b": [0.0, 8.0]}}},
     "grad_norm_ratio"),
    ({"grads": {"q_proj": {"lora_a": [0.0, 0.0], "lora_b": [0.0, 0.0]}}},
     "grad_norm_ratio"),
    ({"grads": {"q_proj": {"lora_a": [-3.0, 0.0], "lora_b": [0.0, -4.0]}}},
     "grad_cosine"),
], ids=["loss", "token_log_probs", "gradient_twice_as_long",
        "zero_gradient", "gradient_reversed"])
def test_compare_train_refuses_each_departure(changed, field):
    import numpy as np

    if "picked" in changed:
        changed["picked"] = np.array(changed["picked"])
    if "grads" in changed:
        changed["grads"] = {k: {a: np.array(b) for a, b in v.items()}
                            for k, v in changed["grads"].items()}
    got = _verdict(**changed)
    assert not got["ok"], (field, got[field])


def test_a_padded_target_is_left_out_of_the_token_comparison():
    import numpy as np

    got = _verdict(picked=np.array([[-1.0, -2.0, -9.0]]))
    assert got["ok"] and got["token_logprob_rms_diff"] == 0.0


def test_packed_rows_keep_documents_apart_and_pad_the_tail():
    rows = check.packed_rows(2, 128, 512, 3, 40)
    assert rows["input_ids"].shape == (2, 128)
    assert (rows["segment_ids"][:, -8:] == 0).all(), "padding at the end"
    assert rows["segment_ids"].max() >= 2, "more than one document a row"
    starts = rows["positions"] == 0
    assert (starts.sum(axis=1) >= 2).all(), "positions restart per document"
    assert (rows["loss_mask"] == (rows["segment_ids"] > 0)).all()


def test_a_later_run_keeps_the_reference_and_computes_the_program_again(
        tmp_path, monkeypatch):
    """What train_cell.check starts: the reference's process once in a
    checkout, the program's process in every run."""
    import harness
    import spec as spec_lib
    import train_cell

    cell = {**spec_lib.resolve_cell("train.mistral_7b.lora_sft"),
            "root": str(tmp_path)}
    started = []

    def fake(self, what, extra, out, timeout_s):
        started.append(what)
        json.dump({"ok": True}, open(out, "w"))

    monkeypatch.setattr(harness.Run, "run_check", fake)
    for _ in range(2):
        run = harness.Run(cell, 1, 1.0, False, True, 0.0)
        assert train_cell.check(run, run.spec["check"])["ok"]
    assert started == ["train-reference", "train-program", "train-program"]
    assert run.notes["reference_check"]["reference_from_cache"] is True
    kept = os.listdir(tmp_path / ".bench_cache" / "checks")
    assert len(kept) == 1, "one file per (configuration, rows)"


# -- the reference takes its sizes from the configuration file -----------------

def _sizes_from_the_programs_model_config(config):
    """What check.py handed the reference before the reference read the
    file itself: the program's own translation of it (kept here as what
    ``sizes(config)`` has to equal for the configurations of that time)."""
    from chip_child import model_fields
    from dlti_tpu.config import ModelConfig

    model_cfg = ModelConfig(**model_fields(config))
    return {"num_layers": model_cfg.num_layers,
            "num_heads": model_cfg.num_heads,
            "num_kv_heads": model_cfg.num_kv_heads,
            "head_dim": model_cfg.resolved_head_dim,
            "rms_norm_eps": model_cfg.rms_norm_eps,
            "rope_theta": model_cfg.rope_theta,
            "sliding_window": model_cfg.sliding_window,
            "tie_embeddings": model_cfg.tie_embeddings}


@pytest.mark.parametrize("name", ["mistral_7b", "qwen2_7b"])
def test_sizes_from_the_file_equal_those_the_program_derived(name):
    import reference

    config = json.load(open(os.path.join(bench_paths.BENCH, "configs",
                                         name + ".json")))
    got = reference.sizes(config)
    want = _sizes_from_the_programs_model_config(config)
    assert got == want
    assert {k: type(v) for k, v in got.items()} == \
        {k: type(v) for k, v in want.items()}
    assert (got["sliding_window"] is None) == (name == "qwen2_7b")


@pytest.mark.parametrize("model", [
    MISTRAL_LIKE, QWEN2_LIKE,
    {k: v for k, v in MISTRAL_LIKE.items() if k != "head_dim"},
    {**QWEN2_LIKE, "tie_word_embeddings": True}],
    ids=["mistral_like", "qwen2_like", "head_dim_left_out", "tied_head"])
def test_forward_on_the_files_sizes_gives_the_same_bits(model):
    """Seeded tiny weights, float32, CPU: the logits with the sizes read
    from the file are those with the sizes the program derived, bit for
    bit, for each key a configuration may leave to its default."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference

    config = {"model": model}
    sizes = reference.sizes(config)
    assert sizes == _sizes_from_the_programs_model_config(config)
    program = check._program_model(config, None)
    params = program.init(jax.random.PRNGKey(3),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    ids = jax.random.randint(jax.random.PRNGKey(4), (96,), 3, 512)
    mine = reference.forward(params, sizes, ids)
    theirs = reference.forward(
        params, _sizes_from_the_programs_model_config(config), ids)
    assert mine.dtype == jnp.float32 and mine.shape == (96, 512)
    assert np.array_equal(np.asarray(mine), np.asarray(theirs))
    # and they are the program's logits, to float32 summation order
    logits, _ = program.apply({"params": params}, ids[None],
                              deterministic=True)
    assert np.abs(np.asarray(logits[0], np.float32)
                  - np.asarray(mine)).max() < 1e-4


def test_a_key_the_reference_needs_and_the_file_lacks_is_an_error():
    import reference

    model = {k: v for k, v in MISTRAL_LIKE.items() if k != "rope_theta"}
    with pytest.raises(KeyError, match="rope_theta"):
        reference.sizes({"model": model})
