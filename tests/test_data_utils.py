"""Data pipeline + utils tests: golden format strings, tokenize/pack
determinism, metrics CSV schema, experiment naming."""

import csv
import os

import numpy as np
import pytest

from dlti_tpu.data import (
    ByteTokenizer,
    format_conversation_for_llama2,
    make_batches,
    tokenize_and_truncate,
)
from dlti_tpu.data.pipeline import pack_sequences, pad_to_batch
from dlti_tpu.utils import (
    MetricsRecord,
    create_experiment_name,
    get_zero_stage_from_config,
    print_metrics_summary,
    save_training_metrics,
)
from dlti_tpu.utils.metrics import compute_mfu


def test_llama2_format_golden():
    """Byte-exact parity with scripts/prepare_dataset.py:12-25."""
    out = format_conversation_for_llama2(
        {"question": "  How do I sort a list? ", "answer": " Use sorted(). "}
    )
    assert out == {"text": "<s>[INST] How do I sort a list? [/INST] Use sorted().</s>"}


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    text = "hello wörld"
    ids = tok.encode(text, add_bos=True, add_eos=True)
    assert ids[0] == tok.bos_id and ids[-1] == tok.eos_id
    assert tok.decode(ids) == text


def test_tokenize_truncates_at_512():
    tok = ByteTokenizer()
    seqs = tokenize_and_truncate(["x" * 1000], tok, max_seq_len=512)
    assert len(seqs[0]) == 512


def test_pad_to_batch_masks():
    ids, mask = pad_to_batch([[5, 6], [7, 8, 9]], seq_len=4, pad_id=0)
    np.testing.assert_array_equal(ids, [[5, 6, 0, 0], [7, 8, 9, 0]])
    np.testing.assert_array_equal(mask, [[1, 1, 0, 0], [1, 1, 1, 0]])


def test_pack_sequences_segments():
    ids, mask, segs = pack_sequences([[1, 2], [3, 4], [5, 6, 7, 8, 9]], seq_len=5, pad_id=0)
    assert ids.shape[1] == 5
    # Docs 1 and 2 pack into one row with distinct segment ids.
    assert segs[0].tolist() == [1, 1, 2, 2, 0]
    assert ids[1].tolist() == [5, 6, 7, 8, 9]
    assert mask[0].tolist() == [1, 1, 1, 1, 0]


def test_batches_shape_and_determinism():
    tok = ByteTokenizer()
    texts = [f"sample number {i}" for i in range(20)]
    ds = make_batches(texts, tok, seq_len=16, micro_batch_size=2,
                      grad_accum_steps=2, shard_by_host=False)
    batches1 = list(ds.epoch(0))
    batches2 = list(ds.epoch(0))
    assert len(batches1) == ds.steps_per_epoch() == 5
    assert batches1[0]["input_ids"].shape == (2, 2, 16)
    np.testing.assert_array_equal(batches1[0]["input_ids"], batches2[0]["input_ids"])
    # Different epoch -> different order.
    batches3 = list(ds.epoch(1))
    assert not all(
        np.array_equal(a["input_ids"], b["input_ids"])
        for a, b in zip(batches1, batches3)
    )


def test_experiment_name_parity():
    """Doctest cases from training/utils.py:22-28 (dev for device)."""
    assert create_experiment_name(1, None) == "baseline"
    assert create_experiment_name(1, 0) == "baseline"
    assert create_experiment_name(2, 1) == "zero1_2dev"
    assert create_experiment_name(4, 3) == "zero3_4dev"


def test_zero_stage_from_config(tmp_path):
    ds_style = tmp_path / "ds.json"
    ds_style.write_text('{"zero_optimization": {"stage": 2}}')
    assert get_zero_stage_from_config(str(ds_style)) == 2
    ours = tmp_path / "ours.json"
    from dlti_tpu.config import preset

    ours.write_text(preset("zero3_8dev").to_json())
    assert get_zero_stage_from_config(str(ours)) == 3


def test_metrics_csv_schema(tmp_path):
    """CSV columns match the reference schema (train_baseline.py:246-255)
    plus the TPU additions."""
    path = str(tmp_path / "m.csv")
    rec = MetricsRecord(
        experiment="zero2_8dev", num_gpus=8, zero_stage=2, strategy="zero2",
        training_time_hours=0.5, samples_per_second=12.0, peak_memory_gb=3.2,
        final_loss=0.71, tokens_per_second_per_chip=800.0, mfu_percent=41.0,
    )
    save_training_metrics(rec, path)
    save_training_metrics(rec, path)
    with open(path) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 2
    ref_cols = ["experiment", "num_gpus", "zero_stage", "strategy",
                "training_time_hours", "samples_per_second", "peak_memory_gb",
                "final_loss"]
    assert list(rows[0].keys())[: len(ref_cols)] == ref_cols
    print_metrics_summary(rec)  # smoke


def test_mfu_formula():
    # 1000 tok/s/chip on a 7e9-param LoRA model at 197 TFLOP/s:
    # 4*7e9*1000 / 197e12 = 14.2%
    mfu = compute_mfu(1000, 7_000_000_000, 197e12, trainable_params=17_000_000)
    np.testing.assert_allclose(mfu, 100 * 4 * 7e9 * 1000 / 197e12, rtol=1e-6)


def test_config_roundtrip():
    from dlti_tpu.config import Config, preset

    cfg = preset("zero2_8dev", model="llama_debug")
    back = Config.from_json(cfg.to_json())
    assert back == cfg


def test_native_packer_matches_python_oracle(monkeypatch):
    """C++ pack assignment == pure-Python packing, bit for bit."""
    import os
    import numpy as np

    from dlti_tpu.data.pipeline import pack_sequences
    from dlti_tpu.utils import native as native_mod

    if native_mod.load_native_runtime() is None:
        import pytest
        pytest.skip("native runtime could not be built here")

    rng = np.random.default_rng(0)
    seqs = [list(map(int, rng.integers(1, 100, rng.integers(1, 40))))
            for _ in range(300)]
    got = pack_sequences(seqs, seq_len=64, pad_id=0, open_rows=8)

    # Force the Python path for the oracle.
    monkeypatch.setenv("DLTI_DISABLE_NATIVE", "1")
    native_mod._TRIED = False
    native_mod._LIB = None
    try:
        want = pack_sequences(seqs, seq_len=64, pad_id=0, open_rows=8)
    finally:
        monkeypatch.delenv("DLTI_DISABLE_NATIVE")
        native_mod._TRIED = False
        native_mod._LIB = None
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_pack_sequences_drops_empty_docs():
    import numpy as np

    from dlti_tpu.data.pipeline import pack_sequences

    ids, mask, segs = pack_sequences([[5], [], [7]], seq_len=4, pad_id=0)
    np.testing.assert_array_equal(ids[0, :2], [5, 7])
    np.testing.assert_array_equal(segs[0, :2], [1, 2])
