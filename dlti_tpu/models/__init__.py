"""Model zoo: Llama-family transformer in Flax + LoRA grafting, the
patterned ``nemotron_h`` family (Mamba-2, routed experts, attention), the
decoder-hybrid-decoder family (``phi4flash``: Mamba-1, differential
attention, gated memory units and cross-attention over one shared pool), the
jamba family (Mamba-1 with inner norms and plain attention, trained with
LoRA over packed rows) and the latent-attention family (``deepseek_v3``: MLA, held gated experts;
``xing4_0``: the same round hyper-connected residual streams)."""

from dlti_tpu.models.llama import LlamaForCausalLM, LlamaModel  # noqa: F401


def build_model(cfg, lora=None, mesh=None):
    """The causal LM a ``ModelConfig`` describes: the one place that picks
    the model class (the trainer, the engine, ``serve.py --random-init``,
    the fleet worker and the benchmark's check all come through here). A
    configuration with a ``kv_lora_rank`` is the latent-attention family;
    one with neither that nor a ``layer_pattern`` is the Llama family; the
    pattern's kinds say which patterned family (``ModelConfig.is_sambay``,
    ``is_jamba``).
    Hyper-connected residual streams (``hc_mult``, ``models.hyper``) are
    wired into the latent-attention family alone."""
    if cfg.hc_mult and not cfg.kv_lora_rank:
        raise NotImplementedError(
            f"hc_mult {cfg.hc_mult}: only the latent-attention family's "
            f"blocks go through stream maps (models.latent.LatentBlock)")
    if cfg.kv_lora_rank:
        from dlti_tpu.models.latent import LatentForCausalLM

        return LatentForCausalLM(cfg, lora, mesh)
    if cfg.is_sambay:
        from dlti_tpu.models.sambay import SambaYForCausalLM

        return SambaYForCausalLM(cfg, lora, mesh)
    if cfg.is_jamba:
        from dlti_tpu.models.jamba import JambaForCausalLM

        return JambaForCausalLM(cfg, lora, mesh)
    if cfg.layer_pattern:
        from dlti_tpu.models.nemotron_h import NemotronHForCausalLM

        return NemotronHForCausalLM(cfg, lora, mesh)
    return LlamaForCausalLM(cfg, lora, mesh)
from dlti_tpu.models.lora import (  # noqa: F401
    LoRADense,
    lora_param_mask,
    merge_lora_params,
    count_params,
)
from dlti_tpu.models.hf_interop import (  # noqa: F401
    config_from_hf,
    config_to_hf,
    graft_base_params,
    load_hf_checkpoint,
    load_peft_adapter,
    params_from_hf_state_dict,
    hf_state_dict_from_params,
    save_hf_checkpoint,
    save_peft_adapter,
)
