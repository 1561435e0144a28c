"""Hugging Face checkpoint interoperability.

The reference consumes stock HF hub checkpoints
(``AutoModelForCausalLM.from_pretrained``, ``training/train_baseline.py:122-126``)
and produces PEFT LoRA adapters (``trainer.save_model``,
``training/train_baseline.py:226-228``). For a reference user to switch to
this framework their artifacts must carry over, both directions:

* :func:`load_hf_checkpoint` / :func:`save_hf_checkpoint` — full-model
  weights in HF Llama layout (safetensors, single file or sharded with an
  ``model.safetensors.index.json``), mapped to/from our Flax param tree.
* :func:`load_peft_adapter` / :func:`save_peft_adapter` — PEFT-format LoRA
  adapters (``adapter_model.safetensors`` + ``adapter_config.json``), mapped
  to/from our in-tree ``lora_a``/``lora_b`` factors.
* :func:`config_from_hf` / :func:`config_to_hf` — ``config.json`` ↔
  :class:`~dlti_tpu.config.ModelConfig`.

Name mapping (HF stores ``(out, in)`` torch kernels; Flax stores
``(in, out)``):

====================================================  =========================================
HF key                                                ours (under ``params``)
====================================================  =========================================
``model.embed_tokens.weight``                         ``model.embed_tokens``
``model.layers.{i}.self_attn.{q,k,v,o}_proj.weight``  ``model.layers_{i}.attn.*.kernel`` (T)
``model.layers.{i}.self_attn.{q,k,v}_proj.bias``      ``model.layers_{i}.attn.*.bias``
``model.layers.{i}.mlp.{gate,up,down}_proj.weight``   ``model.layers_{i}.mlp.*.kernel`` (T)
``model.layers.{i}.input_layernorm.weight``           ``model.layers_{i}.input_norm.scale``
``model.layers.{i}.post_attention_layernorm.weight``  ``model.layers_{i}.post_attn_norm.scale``
``model.norm.weight``                                 ``model.final_norm.scale``
``lm_head.weight``                                    ``lm_head`` (T; absent when tied)
====================================================  =========================================
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Mapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from dlti_tpu.config import LoRAConfig, ModelConfig

_ATTN_PROJS = ("q_proj", "k_proj", "v_proj", "o_proj")
_MLP_PROJS = ("gate_proj", "up_proj", "down_proj")


def _unwrap(params: Mapping[str, Any]) -> Mapping[str, Any]:
    """Accept either the Flax variables dict (``{"params": tree}``) or the
    bare param tree."""
    return params["params"] if "params" in params and "model" not in params else params


from dlti_tpu.utils.dtypes import resolve_dtype as _dtype  # shared table


# ----------------------------------------------------------------------
# config.json <-> ModelConfig
# ----------------------------------------------------------------------

def config_from_hf(hf: Mapping[str, Any], **overrides) -> ModelConfig:
    """Build a :class:`ModelConfig` from an HF ``config.json`` dict."""
    num_heads = hf.get("num_attention_heads", 32)
    kw: Dict[str, Any] = dict(
        vocab_size=hf.get("vocab_size", 32000),
        hidden_size=hf.get("hidden_size", 4096),
        intermediate_size=hf.get("intermediate_size", 11008),
        num_layers=hf.get("num_hidden_layers", 32),
        num_heads=num_heads,
        num_kv_heads=hf.get("num_key_value_heads", num_heads),
        head_dim=hf.get("head_dim"),
        max_seq_len=hf.get("max_position_embeddings", 4096),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
    )
    torch_dtype = hf.get("torch_dtype")
    if torch_dtype in ("float32", "float16", "bfloat16"):
        kw["param_dtype"] = torch_dtype
        if torch_dtype == "float32":
            kw["dtype"] = "float32"
    model_type = hf.get("model_type", "llama")
    if model_type not in ("llama", "mistral", "qwen2", "gemma"):
        # A family we haven't verified forward-pass parity for (gemma2's
        # logit softcapping, phi's partial rotary, ...) must fail loudly,
        # not import as a subtly different model.
        raise NotImplementedError(
            f"model_type {model_type!r} not supported "
            f"(llama/mistral/qwen2/gemma)")
    if model_type == "gemma":
        kw["rmsnorm_offset"] = True       # (1 + w) norm parameterization
        kw["embedding_scale"] = True      # embed * sqrt(hidden)
        kw["tie_embeddings"] = bool(hf.get("tie_word_embeddings", True))
    if hf.get("attention_bias") or model_type == "qwen2":
        kw["attention_bias"] = True
    if hf.get("sliding_window"):
        if model_type == "qwen2":
            # Qwen2 ships sliding_window with use_sliding_window defaulting
            # to *false* (full attention), and when enabled applies it only
            # to layers >= max_window_layers — we support all-or-nothing:
            # mwl <= 0 windows every layer; mwl >= num_layers windows none
            # (common shipped configs set mwl == num_hidden_layers).
            if hf.get("use_sliding_window", False):
                mwl = hf.get("max_window_layers", kw["num_layers"])
                if mwl is None or mwl <= 0:
                    kw["sliding_window"] = int(hf["sliding_window"])
                elif mwl < kw["num_layers"]:
                    raise NotImplementedError(
                        "per-layer sliding window (qwen2 max_window_layers="
                        f"{mwl} of {kw['num_layers']}) is not supported; "
                        "only uniform windows")
                # else: no layer is windowed -> full attention, nothing to set
        elif hf.get("use_sliding_window", True):
            kw["sliding_window"] = int(hf["sliding_window"])
    # Gemma configs prefer "hidden_activation"; transformers force-overrides
    # a null one (and the original-release legacy hidden_act: "gelu") to
    # gelu_pytorch_tanh, so the fallback for gemma must do the same.
    if model_type == "gemma":
        act = hf.get("hidden_activation") or "gelu_pytorch_tanh"
    else:
        act = hf.get("hidden_activation") or hf.get("hidden_act", "silu")
    kw["mlp_activation"] = {
        "silu": "silu", "gelu": "gelu_exact",
        "gelu_pytorch_tanh": "gelu_tanh", "gelu_new": "gelu_tanh",
    }.get(act)
    if kw["mlp_activation"] is None:
        raise NotImplementedError(f"unsupported hidden_act {act!r}")
    kw.update(overrides)
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    unsupported = sorted(set(kw) - known)
    if unsupported:
        # Never drop architecture features silently (a Qwen2 checkpoint
        # without its q/k/v biases would load and be quietly wrong).
        raise NotImplementedError(
            f"checkpoint needs ModelConfig fields not yet supported: "
            f"{unsupported}")
    return ModelConfig(**kw)


def config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    """Emit an HF-style ``config.json`` dict for :func:`save_hf_checkpoint`.

    The model_type tracks the family features so transformers picks a class
    that honors them (qwen2: q/k/v bias; mistral: sliding window)."""
    if cfg.rmsnorm_offset:
        model_type, arch = "gemma", "GemmaForCausalLM"
    elif cfg.attention_bias:
        model_type, arch = "qwen2", "Qwen2ForCausalLM"
    elif cfg.sliding_window:
        model_type, arch = "mistral", "MistralForCausalLM"
    else:
        model_type, arch = "llama", "LlamaForCausalLM"
    out = {
        "architectures": [arch],
        "model_type": model_type,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.resolved_head_dim,
        "max_position_embeddings": cfg.max_seq_len,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "hidden_act": {"silu": "silu", "gelu_exact": "gelu",
                       "gelu_tanh": "gelu_pytorch_tanh"}[cfg.mlp_activation],
        # transformers' Gemma ignores hidden_act and reads this key.
        "hidden_activation": {"silu": "silu", "gelu_exact": "gelu",
                              "gelu_tanh": "gelu_pytorch_tanh"}[cfg.mlp_activation],
        "torch_dtype": {"bfloat16": "bfloat16", "float16": "float16",
                        "float32": "float32"}[cfg.param_dtype],
    }
    if cfg.attention_bias:
        out["attention_bias"] = True
    if cfg.sliding_window:
        out["sliding_window"] = cfg.sliding_window
        # Qwen2 ignores sliding_window unless the flag is set, and applies
        # it only to layers >= max_window_layers — 0 means every layer,
        # matching our uniform window.
        out["use_sliding_window"] = True
        out["max_window_layers"] = 0
    return out


# ----------------------------------------------------------------------
# state dict -> params
# ----------------------------------------------------------------------

def params_from_hf_state_dict(
    state_dict: Mapping[str, Any],
    cfg: ModelConfig,
) -> Dict[str, Any]:
    """Map an HF Llama state dict (numpy/jax arrays) onto our param tree.

    Raises ``KeyError`` on missing weights and ``ValueError`` on unconsumed
    HF keys, so silent architecture mismatches can't slip through.
    """
    dt = _dtype(cfg.param_dtype)
    sd = dict(state_dict)

    def take(key: str, transpose: bool = False):
        w = jnp.asarray(sd.pop(key))
        if transpose:
            w = w.T
        return w.astype(dt)

    model: Dict[str, Any] = {"embed_tokens": take("model.embed_tokens.weight")}
    for i in range(cfg.num_layers):
        hf_l = f"model.layers.{i}"
        attn: Dict[str, Any] = {}
        for p in _ATTN_PROJS:
            attn[p] = {"kernel": take(f"{hf_l}.self_attn.{p}.weight", transpose=True)}
            # q/k/v biases load iff the config declares them (KeyError when
            # declared-but-absent; declared-absent-but-present falls through
            # to the unconsumed-keys check) — bias/config mismatches are
            # never silent. o_proj is biasless in every supported family.
            if cfg.attention_bias and p != "o_proj":
                attn[p]["bias"] = take(f"{hf_l}.self_attn.{p}.bias")
        mlp = {p: {"kernel": take(f"{hf_l}.mlp.{p}.weight", transpose=True)}
               for p in _MLP_PROJS}
        model[f"layers_{i}"] = {
            "attn": attn,
            "mlp": mlp,
            "input_norm": {"scale": take(f"{hf_l}.input_layernorm.weight")},
            "post_attn_norm": {"scale": take(f"{hf_l}.post_attention_layernorm.weight")},
        }
    model["final_norm"] = {"scale": take("model.norm.weight")}

    params: Dict[str, Any] = {"model": model}
    if not cfg.tie_embeddings:
        if "lm_head.weight" in sd:
            params["lm_head"] = take("lm_head.weight", transpose=True)
        else:
            # Some tied checkpoints omit lm_head even when config says untied.
            params["lm_head"] = jnp.asarray(model["embed_tokens"]).T.astype(dt)
    else:
        sd.pop("lm_head.weight", None)
    sd.pop("model.rotary_emb.inv_freq", None)  # derived, never loaded
    leftovers = [k for k in sd if "rotary_emb" not in k]
    if leftovers:
        raise ValueError(f"unconsumed HF weights (architecture mismatch?): "
                         f"{sorted(leftovers)[:8]} (+{max(0, len(leftovers) - 8)} more)")
    return params


def hf_state_dict_from_params(params: Mapping[str, Any],
                              cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    """Our (merged, LoRA-free) param tree -> HF Llama state dict."""
    p = _unwrap(params)
    model = p["model"]
    sd: Dict[str, jnp.ndarray] = {
        "model.embed_tokens.weight": jnp.asarray(model["embed_tokens"]),
        "model.norm.weight": jnp.asarray(model["final_norm"]["scale"]),
    }
    for i in range(cfg.num_layers):
        ours = model[f"layers_{i}"]
        hf_l = f"model.layers.{i}"
        for proj in _ATTN_PROJS:
            leaf = ours["attn"][proj]
            if "lora_a" in leaf:
                raise ValueError("merge LoRA factors before HF export (merge_lora_params)")
            sd[f"{hf_l}.self_attn.{proj}.weight"] = jnp.asarray(leaf["kernel"]).T
            if "bias" in leaf:
                sd[f"{hf_l}.self_attn.{proj}.bias"] = jnp.asarray(leaf["bias"])
        for proj in _MLP_PROJS:
            leaf = ours["mlp"][proj]
            if "lora_a" in leaf:
                raise ValueError("merge LoRA factors before HF export (merge_lora_params)")
            sd[f"{hf_l}.mlp.{proj}.weight"] = jnp.asarray(leaf["kernel"]).T
        sd[f"{hf_l}.input_layernorm.weight"] = jnp.asarray(ours["input_norm"]["scale"])
        sd[f"{hf_l}.post_attention_layernorm.weight"] = jnp.asarray(
            ours["post_attn_norm"]["scale"])
    if not cfg.tie_embeddings and "lm_head" in p:
        sd["lm_head.weight"] = jnp.asarray(p["lm_head"]).T
    return sd


def graft_base_params(params: Dict[str, Any], base: Mapping[str, Any],
                      place=None) -> Dict[str, Any]:
    """Overlay loaded base weights onto a freshly-initialized param tree.

    Leaves present in ``base`` replace the initialized values (with a shape
    check); leaves only in ``params`` (``lora_a``/``lora_b`` factors) keep
    their initialization — the PEFT ``get_peft_model``-on-pretrained
    semantics (``training/train_baseline.py:122-140``). Base leaves with no
    counterpart in the model tree are an architecture mismatch and raise
    (mirroring :func:`params_from_hf_state_dict`'s unconsumed-key check).

    ``place(base_leaf, model_leaf)`` puts one base leaf where its model
    leaf lives (a sharded trainer passes one, so a leaf goes straight to
    its shards); the default casts it on the default device.
    """
    dropped: list = []
    if place is None:
        def place(b, p):
            return jnp.asarray(b).astype(p.dtype)

    def _graft(p, b, path):
        if not isinstance(p, Mapping):
            if hasattr(b, "shape") and tuple(b.shape) != tuple(p.shape):
                raise ValueError(
                    f"{'.'.join(path)}: checkpoint shape {tuple(b.shape)} != "
                    f"model shape {tuple(p.shape)} (wrong ModelConfig?)")
            return place(b, p)
        for k in b:
            if k not in p:
                dropped.append(".".join(path + (k,)))
        return {k: _graft(v, b[k], path + (k,)) if k in b else v
                for k, v in p.items()}

    out = _graft(params, base, ())
    if dropped:
        raise ValueError(
            f"base checkpoint has weights the model tree lacks (architecture "
            f"mismatch?): {dropped[:8]}" +
            (f" (+{len(dropped) - 8} more)" if len(dropped) > 8 else ""))
    return out


# ----------------------------------------------------------------------
# safetensors IO (single-file and HF-sharded)
# ----------------------------------------------------------------------

def _load_safetensors_dir(directory: str) -> Dict[str, jnp.ndarray]:
    from safetensors import safe_open

    index_path = os.path.join(directory, "model.safetensors.index.json")
    single_path = os.path.join(directory, "model.safetensors")
    if os.path.exists(index_path):
        with open(index_path) as f:
            weight_map = json.load(f)["weight_map"]
        shards = sorted(set(weight_map.values()))
    elif os.path.exists(single_path):
        shards = ["model.safetensors"]
    else:
        shards = sorted(f for f in os.listdir(directory) if f.endswith(".safetensors"))
        if not shards:
            raise FileNotFoundError(f"no .safetensors files under {directory}")
    out: Dict[str, jnp.ndarray] = {}
    for shard in shards:
        with safe_open(os.path.join(directory, shard), framework="flax") as f:
            for key in f.keys():
                out[key] = f.get_tensor(key)
    return out


def load_hf_checkpoint(
    directory: str,
    cfg: Optional[ModelConfig] = None,
    **config_overrides,
) -> Tuple[Dict[str, Any], ModelConfig]:
    """Load an HF Llama checkpoint directory -> ``(params, model_config)``.

    ``cfg`` overrides config.json entirely; ``config_overrides`` tweak
    individual fields (e.g. ``max_seq_len=512``, ``dtype="bfloat16"``).
    The two are mutually exclusive.
    """
    if cfg is not None and config_overrides:
        raise ValueError(
            f"pass either cfg or config overrides, not both (got cfg plus "
            f"{sorted(config_overrides)})")
    if cfg is None:
        cfg_path = os.path.join(directory, "config.json")
        with open(cfg_path) as f:
            cfg = config_from_hf(json.load(f), **config_overrides)
    sd = _load_safetensors_dir(directory)
    return params_from_hf_state_dict(sd, cfg), cfg


def save_hf_checkpoint(
    directory: str,
    params: Mapping[str, Any],
    cfg: ModelConfig,
    max_shard_bytes: int = 4 * 1024**3,
) -> None:
    """Write params as an HF-layout checkpoint (config.json + safetensors).

    Shards at ``max_shard_bytes`` with the standard
    ``model-XXXXX-of-XXXXX.safetensors`` + index layout so the output is
    loadable by ``transformers`` / vLLM / the reference stack directly —
    the portable-artifact contract of
    ``stage3_gather_16bit_weights_on_model_save``
    (``configs/ds_config_zero3.json:36``).
    """
    from safetensors.flax import save_file

    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, "config.json"), "w") as f:
        json.dump(config_to_hf(cfg), f, indent=2)

    sd = hf_state_dict_from_params(params, cfg)
    # Greedy sharding by byte size, stable key order.
    shards: list = [[]]
    sizes = [0]
    for key in sd:
        nbytes = int(np.prod(sd[key].shape)) * sd[key].dtype.itemsize
        if sizes[-1] + nbytes > max_shard_bytes and shards[-1]:
            shards.append([])
            sizes.append(0)
        shards[-1].append(key)
        sizes[-1] += nbytes
    if len(shards) == 1:
        save_file(dict(sd), os.path.join(directory, "model.safetensors"))
        return
    weight_map = {}
    n = len(shards)
    for idx, keys in enumerate(shards):
        fname = f"model-{idx + 1:05d}-of-{n:05d}.safetensors"
        save_file({k: sd[k] for k in keys}, os.path.join(directory, fname))
        weight_map.update({k: fname for k in keys})
    with open(os.path.join(directory, "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {"total_size": sum(sizes)},
                   "weight_map": weight_map}, f, indent=2)


# ----------------------------------------------------------------------
# PEFT adapter interop
# ----------------------------------------------------------------------

_PEFT_PREFIX = "base_model.model."


def save_peft_adapter(directory: str, params: Mapping[str, Any],
                      lora: LoRAConfig) -> None:
    """Extract in-tree LoRA factors -> PEFT ``adapter_model.safetensors``.

    Output matches what the reference's ``trainer.save_model`` writes for a
    PEFT-wrapped model (``training/train_baseline.py:226-228``), so adapters
    trained here drop into a PEFT/vLLM stack unchanged.
    """
    from safetensors.flax import save_file

    p = _unwrap(params)
    sd: Dict[str, jnp.ndarray] = {}
    found = set()

    def walk(tree, path):
        if not isinstance(tree, Mapping):
            return
        if "lora_a" in tree and "lora_b" in tree:
            hf_path = _our_path_to_hf(path)
            found.add(path[-1])
            sd[f"{_PEFT_PREFIX}{hf_path}.lora_A.weight"] = jnp.asarray(tree["lora_a"]).T
            sd[f"{_PEFT_PREFIX}{hf_path}.lora_B.weight"] = jnp.asarray(tree["lora_b"]).T
            return
        for k, v in tree.items():
            walk(v, path + (k,))

    walk(p, ())
    if not sd:
        raise ValueError("no LoRA factors in params; nothing to export")
    os.makedirs(directory, exist_ok=True)
    save_file(sd, os.path.join(directory, "adapter_model.safetensors"))
    with open(os.path.join(directory, "adapter_config.json"), "w") as f:
        json.dump({
            "peft_type": "LORA",
            "r": lora.r,
            "lora_alpha": lora.alpha,
            "lora_dropout": lora.dropout,
            # the projections that carry factors: the LoRAConfig's, or the
            # family's own (ModelConfig.lora_targets: jamba's in_proj,
            # x_proj and out_proj beside q/k/v/o)
            "target_modules": [t for t in lora.target_modules if t in found]
            + sorted(found - set(lora.target_modules)),
            "bias": "none",
            "task_type": "CAUSAL_LM",
        }, f, indent=2)


def load_peft_adapter(directory: str, params: Dict[str, Any]) -> Dict[str, Any]:
    """Load a PEFT adapter into an existing param tree (in place of the
    zero-initialized ``lora_a``/``lora_b`` leaves). Returns the tree."""
    from safetensors import safe_open

    with safe_open(os.path.join(directory, "adapter_model.safetensors"),
                   framework="flax") as f:
        sd = {k: f.get_tensor(k) for k in f.keys()}

    p = _unwrap(params)
    for key, w in sd.items():
        stripped = key[len(_PEFT_PREFIX):] if key.startswith(_PEFT_PREFIX) else key
        stripped = stripped.removesuffix(".weight")
        which = None
        for suffix, ours in ((".lora_A", "lora_a"), (".lora_B", "lora_b")):
            if stripped.endswith(suffix):
                stripped, which = stripped.removesuffix(suffix), ours
        if which is None:
            raise ValueError(f"unrecognized adapter key {key}")
        node = _hf_path_to_node(p, stripped)
        if which not in node:
            raise ValueError(
                f"param tree has no {which} at {stripped}; build the model "
                f"with a matching LoRAConfig before loading the adapter")
        expect = node[which].shape
        got = w.T.shape
        if expect != got:
            raise ValueError(f"{key}: shape {got} != expected {expect}")
        node[which] = w.T.astype(node[which].dtype)
    return params


_ATTENTION_PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_proj")


def _our_path_to_hf(path: tuple) -> str:
    """('model','layers_3','attn','q_proj') -> 'model.layers.3.self_attn.q_proj'.
    The jamba family's tree has no ``model`` level and calls a layer's mixer
    ``mixer``: ('layers_3','mixer','in_proj') -> 'model.layers.3.mamba.in_proj'
    and ('layers_7','mixer','q_proj') -> 'model.layers.7.self_attn.q_proj',
    the published names."""
    out = [] if path[0] == "model" else ["model"]
    for part in path:
        if part.startswith("layers_"):
            out.append(f"layers.{part.split('_', 1)[1]}")
        elif part == "attn":
            out.append("self_attn")
        elif part == "mixer":
            out.append("self_attn" if path[-1] in _ATTENTION_PROJECTIONS
                       else "mamba")
        else:
            out.append(part)
    return ".".join(out)


def _hf_path_to_node(tree: Dict[str, Any], hf_path: str) -> Dict[str, Any]:
    """'model.layers.3.self_attn.q_proj' -> the q_proj dict in our tree."""
    parts = hf_path.split(".")
    if parts[0] == "model" and "model" not in tree:
        parts = parts[1:]  # the jamba family's tree has no such level
    node: Any = tree
    i = 0
    while i < len(parts):
        part = parts[i]
        if part == "layers" and i + 1 < len(parts) and parts[i + 1].isdigit():
            part, i = f"layers_{parts[i + 1]}", i + 1
        elif part == "self_attn":
            part = "attn" if "attn" in node else "mixer"
        elif part == "mamba" and "mixer" in node:
            part = "mixer"
        if part not in node:
            raise KeyError(f"{hf_path}: no '{part}' in tree level "
                           f"(have {sorted(node)[:8]})")
        node = node[part]
        i += 1
    return node
