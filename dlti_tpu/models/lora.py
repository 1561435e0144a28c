"""LoRA as a first-class Flax module.

The reference grafts LoRA via PEFT's ``get_peft_model`` with r=16, alpha=32,
dropout=0.05 on q/k/v/o projections, bias "none"
(``training/train_baseline.py:131-140``, ``train_deepspeed_zero3.py:176-185``).
Here LoRA is a native module: :class:`LoRADense` computes

    y = x @ W_base  +  scaling * dropout(x) @ A @ B

with ``A ~ N(0, 1/r)``-style init (kaiming-uniform like PEFT), ``B = 0`` so
training starts at the base model's function, and ``scaling = alpha / r``.

Base kernels live in ``param_dtype`` (bf16, frozen); LoRA factors are fp32
master weights (they are the only trainable/optimized params — the "0.2484%
trainable" property recorded at ``training/train.ipynb:307``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.core import FrozenDict


class LoRADense(nn.Module):
    """Dense layer with an optional LoRA adapter branch."""

    features: int
    use_bias: bool = False
    lora_r: int = 0  # 0 disables the adapter branch
    lora_alpha: int = 32
    lora_dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    lora_param_dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True,
                 adapter_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        in_features = x.shape[-1]
        kernel = self.param(
            "kernel", self.kernel_init, (in_features, self.features), self.param_dtype
        )
        if isinstance(kernel, dict):
            # Weight-only int8 serving: the stored leaf is {"q", "scale"};
            # dequantize at the consumer so only the executing layer holds
            # a compute-dtype copy (dlti_tpu.models.quantization).
            from dlti_tpu.models.quantization import maybe_dequantize

            kernel = maybe_dequantize(kernel, self.dtype, anchor=x)
        y = jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                    preferred_element_type=self.dtype)
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (self.features,), self.param_dtype)
            y = y + bias.astype(self.dtype)

        if self.lora_r > 0:
            # PEFT-style init: A kaiming-uniform, B zeros.
            lora_a = self.param(
                "lora_a",
                nn.initializers.variance_scaling(1.0 / 3.0, "fan_in", "uniform"),
                (in_features, self.lora_r),
                self.lora_param_dtype,
            )
            lora_b = self.param(
                "lora_b", nn.initializers.zeros, (self.lora_r, self.features),
                self.lora_param_dtype,
            )
            h = x
            if self.lora_dropout > 0.0 and not deterministic:
                h = nn.Dropout(rate=self.lora_dropout)(h, deterministic=False)
            # Low-rank branch in compute dtype; r is tiny so this is cheap.
            scaling = self.lora_alpha / self.lora_r
            delta = jnp.dot(
                jnp.dot(h.astype(self.dtype), lora_a.astype(self.dtype),
                        preferred_element_type=self.dtype),
                lora_b.astype(self.dtype),
                preferred_element_type=self.dtype,
            )
            y = y + scaling * delta

        if adapter_ids is not None and self.has_variable("adapters", "a"):
            # Batched multi-LoRA serving (dlti_tpu.serving.adapters): the
            # stacked per-slot A/B pool rides in as an "adapters" variable
            # collection; each batch row gathers ITS adapter's factors by
            # id, so one compiled step serves heterogeneous adapters
            # (S-LoRA/Punica BGMV). Row 0 is all-zero — base requests add
            # exactly +0.0 and stay byte-identical to an adapter-free
            # engine. The branch is Python-static: training and
            # adapter-off serving never trace it.
            pa = self.get_variable("adapters", "a")  # (P, in, r)
            pb = self.get_variable("adapters", "b")  # (P, r, out)
            ps = self.get_variable("adapters", "s")  # (P,)
            a = jnp.take(pa, adapter_ids, axis=0).astype(self.dtype)
            b = jnp.take(pb, adapter_ids, axis=0).astype(self.dtype)
            s = jnp.take(ps, adapter_ids, axis=0).astype(self.dtype)
            h = jnp.einsum("bsi,bir->bsr", x.astype(self.dtype), a,
                           preferred_element_type=self.dtype)
            delta = jnp.einsum("bsr,bro->bso", h, b,
                               preferred_element_type=self.dtype)
            y = y + s[:, None, None] * delta
        return y


# ----------------------------------------------------------------------
# Param-tree utilities
# ----------------------------------------------------------------------

def _is_lora_path(path: tuple) -> bool:
    return any(str(p) in ("lora_a", "lora_b") for p in path)


def lora_param_mask(params) -> Any:
    """Pytree of bools: True for trainable (LoRA) leaves, False for frozen.

    Drives ``optax.masked`` so optimizer state exists only for the ~0.25%
    trainable params — the property that makes ZeRO-1/2 optimizer-state
    sharding compose with LoRA (SURVEY.md §7 hard part #1).
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    if not any(_is_lora_path([getattr(k, "key", k) for k in path]) for path, _ in flat):
        # Full fine-tune (no LoRA grafted): everything trainable.
        return jax.tree_util.tree_map(lambda _: True, params)
    return jax.tree_util.tree_map_with_path(
        lambda path, _: _is_lora_path([getattr(k, "key", k) for k in path]), params
    )


def merge_lora_params(params, scaling: Optional[float] = None, alpha: int = 32):
    """Fold LoRA factors into base kernels: W' = W + scaling * A @ B.

    The TPU-native equivalent of PEFT's ``merge_and_unload`` — produces the
    consolidated checkpoint the serving leg loads (the capability the
    reference gets from ``stage3_gather_16bit_weights_on_model_save``,
    ``configs/ds_config_zero3.json:36``, plus PEFT merge).
    Returns a params tree with ``lora_a``/``lora_b`` removed.
    """
    if isinstance(params, FrozenDict):
        params = params.unfreeze()

    from dlti_tpu.models.quantization import is_quant_node, maybe_dequantize

    def _merge(tree):
        if is_quant_node(tree):
            # int8-frozen-base training: expand back to bf16 so the merged
            # export is a standard compute-dtype tree (serving re-quantizes
            # on load; int8->bf16->int8 round-trips to the same grid).
            return maybe_dequantize(tree, jnp.bfloat16)
        if not isinstance(tree, dict):
            return tree
        out = {}
        has_lora = "lora_a" in tree and "lora_b" in tree and "kernel" in tree
        for k, v in tree.items():
            if has_lora and k in ("lora_a", "lora_b"):
                continue
            if has_lora and k == "kernel":
                if is_quant_node(v):
                    v = maybe_dequantize(v, jnp.bfloat16)
                a = tree["lora_a"].astype(jnp.float32)
                b = tree["lora_b"].astype(jnp.float32)
                r = a.shape[-1]
                s = scaling if scaling is not None else alpha / r
                out[k] = (v.astype(jnp.float32) + s * (a @ b)).astype(v.dtype)
            else:
                out[k] = _merge(v)
        return out

    return _merge(params)


def count_params(params) -> tuple:
    """(trainable, total) param counts, reference-style report
    (``train.ipynb:307``: 16,777,216 / 6,755,192,832 = 0.2484%)."""
    mask = lora_param_mask(params)
    sizes = jax.tree_util.tree_map(lambda x: int(x.size), params)
    total = sum(jax.tree_util.tree_leaves(sizes))
    trainable = sum(
        s for s, m in zip(jax.tree_util.tree_leaves(sizes), jax.tree_util.tree_leaves(mask)) if m
    )
    return trainable, total
