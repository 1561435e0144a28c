"""Hashes of the lowered programs that two cells run, for holding a change
against its parent without a chip: the LoRA train step of the Llama family
over a packed batch (``train.mistral_7b.lora_sft``'s program at test size)
and the decoder-hybrid-decoder family's prefill and decode programs over its
cache (``serve.phi4_mini_flash.reasoning_turns``'s). Run it in both checkouts
(``PYTHONPATH=<checkout> python benchmarks_dev/program_identity.py``): equal
hashes mean the same StableHLO, source locations apart, so the same compiled
program. PR 56 read a0beb4f695b37809, d82666c72e5f8fe0 and ae6470a97272015e
on both sides (PERF.md section 6)."""
import hashlib
import os
import re
import sys

sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from dlti_tpu.config import MODEL_PRESETS, LoRAConfig, OptimizerConfig
from dlti_tpu.models import build_model
from dlti_tpu.ops.kv_cache import init_cache, bind_call
from dlti_tpu.training.optimizer import build_optimizer
from dlti_tpu.training.state import create_train_state
from dlti_tpu.training.step import make_train_step
def h(text):
    text = re.sub(r'loc\(.*?\)|#loc.*|metadata=\{[^}]*\}', '', text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]
# 1. the LoRA train step of the Llama family, packed
cfg = MODEL_PRESETS["llama_tiny"]
model = build_model(cfg, LoRAConfig(enabled=True, r=4, alpha=8))
state = create_train_state(jax.random.PRNGKey(0), model, build_optimizer(OptimizerConfig()), (1, 8))
batch = {k: jnp.ones((1, 2, 64), jnp.int32) for k in ("input_ids", "loss_mask", "positions", "segment_ids")}
step = jax.jit(make_train_step(model, accum_steps=1))
print("train_step llama_tiny", h(step.lower(state, batch, jax.random.PRNGKey(1)).as_text()))
# 2. the decoder-hybrid-decoder family's prefill and decode programs over the cache
cfg = MODEL_PRESETS["sambay_tiny"]
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
cache = init_cache(cfg, 32, 8, 4, jnp.float32, call_tokens=64)
print("cache shapes", h(str(jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), cache))))
groups = [cfg.kv_group_of_layer(i) for i in range(cfg.num_layers)]
for name, s in (("prefill", 16), ("decode", 1)):
    tables = ({"block_tables": jnp.zeros((4, 8), jnp.int32)}, {"block_tables": jnp.zeros((4, 8), jnp.int32), "table_base": jnp.zeros((4,), jnp.int32)})
    bound = bind_call(cache, tables, jnp.arange(4), own_rows=(s == 1), groups=groups)
    # bools in the cache are static: close over them
    static = [{k: v for k, v in e.items() if isinstance(v, bool)} for e in bound]
    arrays = [{k: v for k, v in e.items() if not isinstance(v, bool)} for e in bound]
    fn = jax.jit(lambda p, ids, pos, arrs: model.apply({"params": p}, ids, positions=pos, cache=[{**a, **st} for a, st in zip(arrs, static)], return_counters=True))
    ids = jnp.ones((4, s), jnp.int32); pos = jnp.broadcast_to(jnp.arange(s)[None] + (5 if s == 1 else 0), (4, s))
    print(name, "sambay_tiny", h(fn.lower(params, ids, pos, arrays).as_text()))
