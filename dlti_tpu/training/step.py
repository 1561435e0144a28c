"""The jitted train step: loss, grad accumulation, optimizer update.

This is the in-tree replacement for what the reference outsources to HF
``Trainer`` + the DeepSpeed engine (``trainer.train()``,
``training/train_baseline.py:217``): forward, causal-LM loss with the
collator's semantics (labels = input_ids, ``mlm=False`` —
``train_baseline.py:195-198``), backward w.r.t. the trainable (LoRA) subset
only, gradient accumulation over microbatches (``lax.scan``, matching
``gradient_accumulation_steps`` — ``train_baseline.py:69-75``), global-norm
clip, AdamW update.

Design notes (TPU-first):

* Gradients are computed only for the trainable flat subset — backprop flows
  *through* frozen bf16 base kernels but never materializes their dW, the
  same work-skipping PEFT gets from ``requires_grad=False``.
* Grad accumulation is a ``lax.scan`` over the leading ``accum`` axis of the
  batch, accumulating fp32 grads; one compiled program per optimizer step,
  no host round-trips.
* Everything is shape-static; the same step function is jitted per-device or
  ``jit``-over-a-``Mesh`` with sharding constraints (see
  ``dlti_tpu.parallel``).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax

from dlti_tpu.training.state import TrainState, combine_params


def causal_lm_loss(
    logits: jnp.ndarray,
    input_ids: jnp.ndarray,
    loss_mask: Optional[jnp.ndarray] = None,
) -> tuple:
    """Next-token cross-entropy.

    Labels are the inputs shifted left (HF ``DataCollatorForLanguageModeling``
    with ``mlm=False`` shifts inside the model; semantics identical).
    Returns (sum_loss, num_tokens) so callers can weight across microbatches.
    """
    targets = input_ids[:, 1:]
    logits = logits[:, :-1, :]
    if loss_mask is None:
        mask = jnp.ones_like(targets, dtype=jnp.float32)
    else:
        mask = loss_mask[:, 1:].astype(jnp.float32)
    token_loss = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    return jnp.sum(token_loss * mask), jnp.sum(mask)


def chunked_causal_lm_loss(
    hidden: jnp.ndarray,
    lm_head: jnp.ndarray,
    input_ids: jnp.ndarray,
    loss_mask: Optional[jnp.ndarray] = None,
    chunk: int = 128,
) -> tuple:
    """:func:`causal_lm_loss` without ever materializing (B, S, V) logits.

    The LM-head matmul + softmax-CE run per sequence chunk inside a
    rematerialized ``lax.scan``: peak fp32 logit memory drops from
    S*vocab to chunk*vocab per example, and the backward recomputes each
    chunk's logits instead of storing them. Identical math to the
    unchunked loss up to summation order. From shapes alone, at
    micro-batch 8 x seq 512 x vocab 32k the fp32 logits and their gradient
    are ~1 GB together.

    Not for sequence-parallel runs: the chunk reshape would regather a
    'sequence'-sharded activation.
    """
    x = hidden[:, :-1, :]
    targets = input_ids[:, 1:]
    if loss_mask is None:
        mask = jnp.ones_like(targets, dtype=jnp.float32)
    else:
        mask = loss_mask[:, 1:].astype(jnp.float32)
    b, s1, h = x.shape
    n = -(-s1 // chunk)
    pad = n * chunk - s1
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    xs = x.reshape(b, n, chunk, h).transpose(1, 0, 2, 3)
    ts = targets.reshape(b, n, chunk).transpose(1, 0, 2)
    ms = mask.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        xc, tc, mc = inp
        logits = jnp.dot(xc, lm_head,
                         preferred_element_type=jnp.float32).astype(jnp.float32)
        tl = optax.softmax_cross_entropy_with_integer_labels(logits, tc)
        return (carry[0] + jnp.sum(tl * mc), carry[1] + jnp.sum(mc)), None

    (loss_sum, n_tok), _ = jax.lax.scan(
        jax.checkpoint(body), (jnp.float32(0.0), jnp.float32(0.0)),
        (xs, ts, ms))
    return loss_sum, n_tok


def apply_loss_scaler(scaler: dict, grad_norm, new_trainable, old_trainable,
                      new_opt_state, old_opt_state,
                      scale_window: int, min_scale: float, hysteresis: int):
    """Dynamic fp16 loss-scaler update (exact ds_config semantics:
    ``configs/ds_config_zero1.json:25-32``) — shared by the flat and
    pipelined train steps.

    On overflow (non-finite grad norm) the optimizer update is skipped
    (params/opt state keep old values) and the scale halves once the
    hysteresis budget is spent; after ``scale_window`` consecutive good
    steps the scale doubles. Returns
    ``(trainable, opt_state, new_scaler, metrics_extra)``.
    """
    finite = jnp.isfinite(grad_norm)
    new_trainable = jax.tree_util.tree_map(
        lambda new, old: jnp.where(finite, new, old),
        new_trainable, old_trainable)
    new_opt_state = jax.tree_util.tree_map(
        lambda new, old: jnp.where(finite, new, old)
        if hasattr(new, "shape") else new,
        new_opt_state, old_opt_state)

    # Overflow: absorb into hysteresis first, then halve the scale.
    hyst_after = jnp.where(finite, scaler["hysteresis_left"],
                           jnp.maximum(scaler["hysteresis_left"] - 1, 0))
    shrink = (~finite) & (scaler["hysteresis_left"] <= 1)
    scale_after = jnp.where(
        shrink, jnp.maximum(scaler["scale"] * 0.5, min_scale),
        scaler["scale"])
    good_after = jnp.where(finite, scaler["good_steps"] + 1, 0)
    # Growth: double after scale_window consecutive good steps.
    grow = good_after >= scale_window
    new_scaler = {
        "scale": jnp.where(grow, scale_after * 2.0, scale_after),
        "good_steps": jnp.where(grow, 0, good_after),
        # Any scale change re-arms the hysteresis budget.
        "hysteresis_left": jnp.where(
            shrink | grow, jnp.int32(hysteresis), hyst_after),
    }
    metrics_extra = {"loss_scale": new_scaler["scale"],
                     "overflow": (~finite).astype(jnp.float32)}
    return new_trainable, new_opt_state, new_scaler, metrics_extra


def guard_nonfinite_update(grad_norm, loss, new_trainable, old_trainable,
                           new_opt_state, old_opt_state):
    """bf16-path nonfinite gate: skip the optimizer update when the loss
    or grad norm is nonfinite, exactly as :func:`apply_loss_scaler` has
    always done for fp16 overflow — without it a single NaN batch writes
    NaN into every AdamW moment and the run is numerically dead from then
    on. Params/opt state keep their old values; the step counter still
    advances (the lr/rng schedule is a pure function of the step index,
    so skipping is rollback- and world-size-invariant). Returns
    ``(trainable, opt_state, metrics_extra)`` with the ``nonfinite`` /
    ``skipped_update`` flags the host-side sentinel
    (``dlti_tpu.training.sentinel``) reads from the already-synced
    metrics."""
    finite = jnp.isfinite(grad_norm) & jnp.isfinite(loss)
    new_trainable = jax.tree_util.tree_map(
        lambda new, old: jnp.where(finite, new, old),
        new_trainable, old_trainable)
    new_opt_state = jax.tree_util.tree_map(
        lambda new, old: jnp.where(finite, new, old)
        if hasattr(new, "shape") else new,
        new_opt_state, old_opt_state)
    bad = (~finite).astype(jnp.float32)
    return new_trainable, new_opt_state, {
        "nonfinite": bad, "skipped_update": bad}


def make_train_step(
    model,
    *,
    accum_steps: int = 1,
    sharding_constraint: Optional[Callable] = None,
    grad_constraint: Optional[Callable] = None,
    fp16_scale_window: int = 1000,
    fp16_min_scale: float = 1.0,
    fp16_hysteresis: int = 2,
    loss_chunk: int = 0,
) -> Callable:
    """Build ``train_step(state, batch, rng) -> (state, metrics)``.

    ``batch`` is a dict with ``input_ids`` (accum, micro_bs, seq) int32 and
    optional ``loss_mask`` of the same shape. ``sharding_constraint`` is an
    optional fn applied to per-microbatch inputs (inserted by the parallel
    layer to pin activations to the mesh). ``grad_constraint`` pins the
    accumulated grads to the optimizer-state sharding — the ZeRO-2
    reduce-scatter semantics (``configs/ds_config_zero1.json:40``).
    Host offload (``configs/ds_config_zero3.json:19-27``) is wired by the
    sharded-step wrapper (``make_sharded_train_step``), not here: when the
    runtime supports host-memory compute operands the frozen params enter
    the compiled program directly from pinned host memory (in-step
    streaming); otherwise the wrapper moves host-resident state to HBM at
    the step boundary and back after.

    When ``state.scaler`` is set (fp16 training), the loss is multiplied by
    the dynamic scale before backward, grads are unscaled, and non-finite
    grads skip the update and shrink the scale — DeepSpeed's dynamic loss
    scaler (``configs/ds_config_zero1.json:25-32``): halve on overflow once
    ``hysteresis`` overflows have been absorbed, double after
    ``fp16_scale_window`` consecutive good steps.
    """

    model_cfg = getattr(model, "cfg", None)
    moe_coef = (model_cfg.router_aux_loss_coef
                if model_cfg is not None and model_cfg.num_experts > 0 else 0.0)
    if loss_chunk and moe_coef:
        raise ValueError(
            "loss_chunk does not compose with MoE aux-loss collection; "
            "set train.loss_chunk=0 for MoE models")
    # What the model counts in a training pass (``models.jamba``: the
    # documents that start inside the rows), summed over the microbatches
    # into the step's metrics under the counters' own names.
    counted = tuple(getattr(model, "train_counters", ()))

    def microbatch_loss(trainable, frozen, micro, rng):
        params = combine_params(trainable, frozen)
        input_ids = micro["input_ids"]
        loss_mask = micro.get("loss_mask")
        if sharding_constraint is not None:
            input_ids = sharding_constraint(input_ids)
        apply_kwargs = dict(
            positions=micro.get("positions"),  # packed: per-doc RoPE restart
            segment_ids=micro.get("segment_ids"),  # packed: intra-doc attention
            deterministic=False,
            rngs={"dropout": rng},
        )
        if counted:
            apply_kwargs["return_counters"] = True
        if moe_coef and loss_mask is not None and micro.get("segment_ids") is None:
            # Keep padding tokens out of expert capacity/aux statistics.
            # Only for unpacked batches, where loss_mask IS the padding
            # mask; packed batches zero loss_mask at every document's
            # first (real!) token, and the model derives the correct
            # padding mask from segment_ids instead.
            apply_kwargs["token_mask"] = loss_mask
        if moe_coef:
            # MoE: collect the sown per-layer router load-balance losses
            # (dlti_tpu.models.moe.MoEMLP) alongside the LM loss.
            ((logits, _), variables) = model.apply(
                {"params": params}, input_ids,
                mutable=["intermediates"], **apply_kwargs,
            )
            from dlti_tpu.models.moe import collect_aux_loss

            aux = collect_aux_loss(variables.get("intermediates", {}))
        elif loss_chunk:  # MoE+loss_chunk rejected at build time above
            hidden, *rest = model.apply({"params": params}, input_ids,
                                        return_hidden=True, **apply_kwargs)
            aux = 0.0
        else:
            logits, *rest = model.apply({"params": params}, input_ids,
                                        **apply_kwargs)
            aux = 0.0
        counts = {name: rest[1][name].astype(jnp.float32)
                  for name in counted}
        if loss_chunk:
            loss_sum, n_tok = chunked_causal_lm_loss(
                hidden, model.head_matrix(params, hidden),
                input_ids, loss_mask, loss_chunk)
        else:
            loss_sum, n_tok = causal_lm_loss(logits, input_ids, loss_mask)
        # Weight the (per-microbatch mean) aux loss by tokens so the final
        # /n_tok gives ce_mean + coef * token-weighted-mean(aux). The
        # differentiated objective carries the aux term; reported metrics
        # keep CE and aux separate so logged losses stay comparable with
        # dense runs and the reference's pure-CE trajectory.
        objective = loss_sum + moe_coef * aux * n_tok
        return objective, (loss_sum, aux * n_tok, n_tok, counts)

    def train_step(state: TrainState, batch: dict, rng: jax.Array):
        trainable, frozen = state.trainable_and_frozen()
        opt_state = state.opt_state
        loss_scale = (state.scaler["scale"] if state.scaler is not None
                      else jnp.float32(1.0))

        def accum_body(carry, micro_with_rng):
            # One fused fwd+bwd per microbatch via value_and_grad.
            grads_acc, loss_acc, aux_acc, tok_acc, counts_acc = carry
            micro, micro_rng = micro_with_rng

            def scaled_loss(trainable, frozen, micro, rng):
                objective, parts = microbatch_loss(trainable, frozen, micro, rng)
                return objective * loss_scale, parts

            (_, (loss_sum, aux_sum, n_tok, counts)), grads = \
                jax.value_and_grad(scaled_loss, argnums=0, has_aux=True)(
                    trainable, frozen, micro, micro_rng)
            grads_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
            )
            return (grads_acc, loss_acc + loss_sum, aux_acc + aux_sum,
                    tok_acc + n_tok,
                    {k: counts_acc[k] + v for k, v in counts.items()}), None

        zero_grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), trainable
        )
        zero_carry = (zero_grads, jnp.float32(0.0), jnp.float32(0.0),
                      jnp.float32(0.0), dict.fromkeys(counted,
                                                      jnp.float32(0.0)))
        rngs = jax.random.split(rng, accum_steps)
        if accum_steps == 1:
            micro = jax.tree_util.tree_map(lambda x: x[0], batch)
            (grads, loss_sum, aux_sum, n_tok, counts), _ = accum_body(
                zero_carry, (micro, rngs[0])
            )
        else:
            (grads, loss_sum, aux_sum, n_tok, counts), _ = jax.lax.scan(
                accum_body, zero_carry, (batch, rngs),
            )

        # Mean over all tokens in the global batch (matches HF Trainer's
        # token-mean loss under grad accumulation). Grads also unscale the
        # fp16 loss scale here (no-op at scale 1).
        n_tok = jnp.maximum(n_tok, 1.0)
        grads = jax.tree_util.tree_map(lambda g: g / (n_tok * loss_scale), grads)
        loss = loss_sum / n_tok
        if grad_constraint is not None:
            grads = grad_constraint(grads)

        updates, new_opt_state = state.tx.update(grads, opt_state, trainable)
        new_trainable = optax.apply_updates(trainable, updates)

        grad_norm = optax.global_norm(grads)
        metrics = {
            "loss": loss,  # pure token-mean CE (aux reported separately)
            "grad_norm": grad_norm,
            "num_tokens": n_tok,
        }
        if moe_coef:
            metrics["aux_loss"] = aux_sum / n_tok
        metrics.update(counts)

        new_scaler = state.scaler
        if state.scaler is not None:
            new_trainable, new_opt_state, new_scaler, extra = \
                apply_loss_scaler(
                    state.scaler, grad_norm, new_trainable, trainable,
                    new_opt_state, opt_state, fp16_scale_window,
                    fp16_min_scale, fp16_hysteresis)
            metrics.update(extra)
            # Uniform sentinel schema with the bf16 path: an fp16
            # overflow IS a skipped nonfinite step.
            metrics["nonfinite"] = extra["overflow"]
            metrics["skipped_update"] = extra["overflow"]
        else:
            # bf16 path: same skip semantics, no scale to evolve.
            new_trainable, new_opt_state, extra = guard_nonfinite_update(
                grad_norm, loss, new_trainable, trainable,
                new_opt_state, opt_state)
            metrics.update(extra)

        new_params = combine_params(new_trainable, frozen)
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt_state,
            scaler=new_scaler,
        )
        return new_state, metrics

    return train_step


def make_multi_step(step_fn: Callable) -> Callable:
    """Scan K whole train steps into ONE compiled program.

    ``multi(state, batches, rngs)``: ``batches`` is a step-stacked batch
    pytree (leading axis K) and ``rngs`` a (K, ...) key array; returns the
    state after K steps plus step-stacked metrics. The training analog of
    the serving engine's multi-step decode: every compiled-program call
    pays a fixed host dispatch and sync cost, and the scan amortizes it
    K-fold (how large that cost is on the chip is not measured). The
    trajectory equals K separate calls when
    the caller pre-splits the same per-step rngs; a jitted ``step_fn`` is
    traced inline, keeping its sharding constraints.
    """

    def multi(state, batches, rngs):
        def body(st, inp):
            b, r = inp
            return step_fn(st, b, r)

        return jax.lax.scan(body, state, (batches, rngs))

    return jax.jit(multi, donate_argnums=(0,))


def make_eval_step(model, loss_chunk: int = 0) -> Callable:
    """Build ``eval_step(state, batch) -> metrics`` (no dropout, no update).

    ``loss_chunk`` mirrors the train step: a run whose HBM budget depends
    on never materializing full fp32 logits must not OOM at its first
    periodic eval.
    """

    def eval_step(state: TrainState, batch: dict):
        kwargs = dict(
            positions=batch.get("positions"),
            segment_ids=batch.get("segment_ids"),
            deterministic=True,
        )
        if loss_chunk:
            hidden, _ = model.apply(
                {"params": state.params}, batch["input_ids"],
                return_hidden=True, **kwargs)
            loss_sum, n_tok = chunked_causal_lm_loss(
                hidden, model.head_matrix(state.params, hidden),
                batch["input_ids"], batch.get("loss_mask"), loss_chunk)
        else:
            logits, _ = model.apply(
                {"params": state.params}, batch["input_ids"], **kwargs)
            loss_sum, n_tok = causal_lm_loss(
                logits, batch["input_ids"], batch.get("loss_mask")
            )
        return {"loss": loss_sum / jnp.maximum(n_tok, 1.0), "num_tokens": n_tok}

    return eval_step
