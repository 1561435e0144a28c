"""Parameters, operations and bytes of the jamba family (``model_type``
jamba: Mamba-1 layers with an attention layer among every
``attn_layer_period``, a gate/up/down MLP in every layer), from the
configuration file's shapes and a training window's documents.

Each count is the least any program has to do (a floor must not overstate,
so that a share of a peak computed from it cannot pass 100 %), and none
depends on what implements the scan:

- ``kinds``: the mixer of each layer, from ``attn_layer_period`` and
  ``attn_layer_offset`` (the reference derives the same).
- ``parameters``: the model, by part; what the program's
  ``ModelConfig.num_params`` and the configuration file's ``deployment``
  must agree with.
- ``matmul_parameters``: the parameters a token is MULTIPLIED by (the
  projections of every layer and the tied head); the convolution, the norms,
  the biases, ``A_log`` and ``D`` act element by element and count nothing.
- ``lora_parameters``: adapter parameters of rank r on the projections the
  configuration names (``program.lora_targets``).
- ``train_flops_per_token``: a LoRA step over a frozen base: forward 2 and
  backward 2 (activation gradients alone) a matmul parameter, 6 an adapter
  parameter, attention by the band packed documents leave, in the attention
  layers alone, forward and backward (x 3). **The scan's element-by-element
  work (``d_inner x N`` multiply-adds and exponentials a token a Mamba layer)
  is not matrix-unit work and is left out**: the share of the peak that
  ``mfu_pct.train.ssm`` reports is of the matrix units, and time the vector
  unit spends in the scan lowers it.
- ``scan_bytes_per_token``: what the scan of ONE Mamba layer must move a
  token, once forward and once backward, in float32: forward reads u', Dt
  and z (``d_inner`` each), B and C (N each) and writes y; backward reads
  the five again and dy and writes their five gradients. States kept at
  chunk boundaries and states recomputed are the implementation's and are
  not counted.
- ``documents_per_step``: documents that start in a step's rows, from the
  window's documents: what the counter ``recurrent_state_resets`` should
  read in the mean.

The standard library alone; sizes come from ``config["model"]`` (the
published keys as run), never from the program.
"""

from __future__ import annotations

FLOAT32 = 4


def is_family(config: dict) -> bool:
    return config["model"].get("model_type") == "jamba"


def sizes(config: dict) -> dict:
    m = config["model"]
    h, heads = m["hidden_size"], m["num_attention_heads"]
    return {"h": h, "m": m["intermediate_size"], "vocab": m["vocab_size"],
            "layers": m["num_hidden_layers"], "heads": heads,
            "kv_heads": m["num_key_value_heads"],
            "dh": m.get("head_dim") or h // heads,
            "d_inner": m["mamba_expand"] * h, "n": m["mamba_d_state"],
            "k": m["mamba_d_conv"], "r": m["mamba_dt_rank"]}


def kinds(config: dict) -> list:
    """``mamba | attention`` a layer."""
    m = config["model"]
    period, offset = m["attn_layer_period"], m["attn_layer_offset"]
    return ["attention" if l % period == offset else "mamba"
            for l in range(m["num_hidden_layers"])]


def projections(config: dict) -> dict:
    """``{kind: {projection: (inputs, outputs)}}`` of one layer: its mixer's
    projections and the MLP's, by the names of the program's tree."""
    z = sizes(config)
    h, d, q, kv = z["h"], z["d_inner"], z["heads"] * z["dh"], \
        z["kv_heads"] * z["dh"]
    mlp = {"gate_proj": (h, z["m"]), "up_proj": (h, z["m"]),
           "down_proj": (z["m"], h)}
    return {
        "mamba": {"in_proj": (h, 2 * d), "x_proj": (d, z["r"] + 2 * z["n"]),
                  "dt_proj": (z["r"], d), "out_proj": (d, h), **mlp},
        "attention": {"q_proj": (h, q), "k_proj": (h, kv),
                      "v_proj": (h, kv), "o_proj": (q, h), **mlp}}


def parameters(config: dict) -> dict:
    """The model's parameters by part, and their ``total``."""
    z = sizes(config)
    h, d = z["h"], z["d_inner"]
    proj = projections(config)
    matmul = {kind: sum(a * b for a, b in p.values())
              for kind, p in proj.items()}
    # conv kernel and bias, the three inner norms, dt_bias, A_log, D
    other = {"mamba": d * (z["k"] + 1) + z["r"] + 2 * z["n"] + d
             + d * z["n"] + d, "attention": 0}
    count = {kind: kinds(config).count(kind) for kind in proj}
    out = {
        "embedding": z["vocab"] * h,
        "mamba_layers": count["mamba"] * (
            matmul["mamba"] + other["mamba"] + 2 * h),
        "attention_layers": count["attention"] * (
            matmul["attention"] + 2 * h),
        "final_norm": h,
    }
    if not config["model"].get("tie_word_embeddings", False):
        out["head"] = z["vocab"] * h
    out["total"] = sum(out.values())
    return out


def matmul_parameters(config: dict) -> int:
    """Parameters a token is multiplied by: every projection, and the head
    (the embedding again where it is tied)."""
    z = sizes(config)
    proj = projections(config)
    return sum(sum(a * b for a, b in proj[kind].values())
               for kind in kinds(config)) + z["h"] * z["vocab"]


def lora_parameters(config: dict, r: int) -> int:
    """Adapter parameters of rank ``r``: ``r x (inputs + outputs)`` for
    each projection of each layer that ``program.lora_targets`` names."""
    targets = set(config["program"]["lora_targets"])
    proj = projections(config)
    return sum(r * (a + b) for kind in kinds(config)
               for name, (a, b) in proj[kind].items() if name in targets)


def attention_flops_per_token(config: dict, keys_seen: float) -> float:
    """Forward AND backward of the attention layers' score and value
    products over ``keys_seen`` keys a query in the mean."""
    z = sizes(config)
    forward = 2 * 2 * z["heads"] * z["dh"] * keys_seen \
        * kinds(config).count("attention")
    return 3 * forward


def train_flops_per_token(config: dict, lora_r: int,
                          keys_seen: float) -> float:
    return (4 * matmul_parameters(config)
            + 6 * lora_parameters(config, lora_r)
            + attention_flops_per_token(config, keys_seen))


def scan_bytes_per_token(config: dict) -> int:
    """Bytes the scan of one Mamba layer must move a token, forward once
    and backward once (float32)."""
    z = sizes(config)
    d, n = z["d_inner"], z["n"]
    forward = (3 * d + 2 * n) + d               # u', Dt, z, B, C in; y out
    backward = (3 * d + 2 * n) + d + (3 * d + 2 * n)  # those, dy; gradients
    return FLOAT32 * (forward + backward)


def scan_bytes_per_step(config: dict, tokens: float) -> float:
    return (scan_bytes_per_token(config) * tokens
            * kinds(config).count("mamba"))


def document_lengths(texts: list, seq_len: int) -> list:
    """Tokens the byte tokenizer makes of each document (BOS + bytes + EOS,
    truncated to a row)."""
    return [min(len(t.encode("utf-8")) + 2, seq_len) for t in texts]


def documents_per_step(texts: list, seq_len: int,
                       tokens_per_step: float) -> float:
    """Documents that start in a step's rows in the mean: the step's real
    tokens over the mean document."""
    lengths = document_lengths(texts, seq_len)
    return tokens_per_step * len(lengths) / sum(lengths)
