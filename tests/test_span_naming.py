"""Static guard: every ``tracer.span/complete/instant`` call-site name in
the package is pinned here, and every ``account.phase`` name with them (a
phase of the stepper's clock is its span of the same name while the tracer
is enabled: ``telemetry.ledger.StepperAccount``).

The goodput ledger and the critical-path attribution parse span names
("train/*" phases, "engine/*" step phases, "request/*" lifecycle,
"gateway/*" admission); flight-record readers and ``scripts/postmortem.py``
group by them too. Like ``test_metric_naming.py`` for the ``/metrics``
exposition, this walk makes instrumentation names a *contract*: adding a
span site means adding its name to the catalog (deliberate), and a rename
fails here before it silently breaks attribution parsing or saved-trace
tooling.

The walk is an AST scan, not an import: a span behind a rarely-taken
branch is still caught, and the guard costs no jax startup.
"""

import ast
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "dlti_tpu")

# The catalog. Names group as "<plane>/<phase>"; every one is emitted via
# the process-global SpanTracer (telemetry.tracer).
SPAN_NAME_CATALOG = frozenset({
    # Trainer per-step phases (also the goodput ledger's bucket sites).
    "train/batch_fetch",
    "train/host_to_device",
    "train/step_dispatch",
    "train/device_sync",
    "train/eval",
    "train/checkpoint_save",
    "train/sdc_probe",
    "train/sentinel_rollback",
    "train/prefetch",
    "train/bookkeep",
    # Engine step phases + the prefix-tier restore charge.
    "engine/admit",
    "engine/adapter_load",
    "engine/kv_handoff",
    "engine/prefill_chunks",
    "engine/tier_restore",
    # The release of a window group's blocks behind the window (inside
    # engine/decode_plan and engine/prefill_launch; PR 46).
    "engine/window_free",
    # The children of the step phases: where the host's time between two
    # device programs goes (benchmark/lib/span_rules.json reads them).
    "engine/decode_prep",
    "engine/decode_plan",
    "engine/decode_assemble",
    "engine/decode_stage",
    "engine/decode_launch",
    "engine/decode_wait",
    "engine/decode_emit",
    "engine/prefill_group",
    "engine/prefill_launch",
    "engine/prefill_wait",
    # The stepper thread's loop around engine.step() (serving.server).
    "server/wait_work",
    "server/step",
    "server/lock_wait",
    "server/drain_events",
    # A host phase of the stepper that stood still (telemetry.ledger).
    "server/stall",
    # A collection of the cyclic collector (telemetry.ledger's gc hook).
    "gc/collect",
    # Marks that cut the ring to a profiler capture (telemetry.tracer).
    "profiler/start",
    "profiler/stop",
    # Request lifecycle (telemetry.lifecycle).
    "request/submitted",
    "request/queued",
    "request/readmitted",
    "request/prefill",
    "request/decode",
    "request/preempted",
    # Admission gateway.
    "gateway/enqueued",
    "gateway/queued",
    "gateway/rejected",
    "gateway/shed",
    # Watchdog alert instants.
    "watchdog/alert",
})

_TRACER_METHODS = ("span", "complete", "instant", "phase")

# Call sites whose first argument is not a string literal, allowed ONLY
# because their name is a literal *default* elsewhere (asserted below):
# (relative path, receiver attribute) -> the default-carrying symbol.
_DYNAMIC_ALLOWED = {
    # HostPrefetcher worker span: self._tracer.span(self._span_name, ...)
    # with span_name="train/prefetch" in the constructor signature.
    os.path.join("data", "prefetch.py"),
    # The stepper account's one helper: a phase opens the tracer's span
    # under the phase's own name, and that name is a literal at every
    # ``phase(...)`` call site, which this walk pins like a span's.
    os.path.join("telemetry", "ledger.py"),
}


def _walk_calls():
    """Yield (relpath, lineno, first_arg_node) for every
    ``<obj>.span|complete|instant|phase(...)`` call in the package."""
    for root, _dirs, files in os.walk(PKG):
        if "__pycache__" in root:
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            rel = os.path.relpath(path, PKG)
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                # (``phase`` is also called through a local alias of the
                # account's bound method on the stepper's path)
                if not (isinstance(func, ast.Attribute)
                        and func.attr in _TRACER_METHODS
                        or isinstance(func, ast.Name)
                        and func.id == "phase"):
                    continue
                if not node.args:
                    continue
                yield rel, node.lineno, node.args[0]


def _collected():
    literals = {}
    dynamic = []
    for rel, lineno, arg in _walk_calls():
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            # Only slash-namespaced strings are span names; this keeps
            # unrelated `.complete(x)`-shaped methods (none today) from
            # polluting the walk if one ever appears.
            if "/" in arg.value:
                literals.setdefault(arg.value, []).append((rel, lineno))
        else:
            dynamic.append((rel, lineno))
    return literals, dynamic


def test_every_span_call_site_name_is_pinned():
    literals, dynamic = _collected()
    unknown = set(literals) - SPAN_NAME_CATALOG
    assert not unknown, (
        f"span names not in the pinned catalog: "
        f"{ {n: literals[n] for n in unknown} } — ledger/attribution and "
        f"postmortem tooling parse span names; add new ones to "
        f"SPAN_NAME_CATALOG deliberately")
    missing = SPAN_NAME_CATALOG - set(literals) - {"train/prefetch"}
    assert not missing, (
        f"catalog names with no remaining call site: {missing} — a "
        f"renamed/removed span breaks attribution parsing; update the "
        f"catalog with the rename")
    for rel, lineno in dynamic:
        assert rel in _DYNAMIC_ALLOWED, (
            f"non-literal span name at dlti_tpu/{rel}:{lineno} — span "
            f"names are a static contract; use a literal (or add an "
            f"allowlist entry with its literal default pinned)")


def test_dynamic_prefetch_span_default_is_pinned():
    """The one allowed dynamic site (HostPrefetcher) must keep its
    literal default in the constructor signature."""
    import inspect

    from dlti_tpu.data.prefetch import HostPrefetcher

    sig = inspect.signature(HostPrefetcher.__init__)
    assert sig.parameters["span_name"].default == "train/prefetch"
    assert "train/prefetch" in SPAN_NAME_CATALOG


def test_span_names_follow_plane_slash_phase_convention():
    for name in SPAN_NAME_CATALOG:
        plane, _, phase = name.partition("/")
        assert plane and phase, name
        assert plane in ("train", "engine", "request", "gateway",
                         "watchdog", "server", "profiler", "gc"), name
        assert phase == phase.lower().replace("-", "_"), name


def test_walk_actually_sees_known_sites():
    """Anti-vacuity: the AST walk finds the long-standing sites (an empty
    walk would pass the guards above trivially)."""
    literals, _ = _collected()
    for expected in ("train/step_dispatch", "engine/admit",
                     "request/queued", "gateway/enqueued",
                     "watchdog/alert", "engine/tier_restore",
                     "engine/kv_handoff"):
        assert expected in literals, f"walk missed {expected}"
    # The kv-handoff span is emitted from BOTH handoff paths — the
    # disagg prefill->decode staging injection and the fleet drain
    # migration (the distributed trace's cross-process leg); losing
    # either call site breaks per-request timeline reconstruction.
    handoff_files = {rel for rel, _ in literals["engine/kv_handoff"]}
    for rel in (os.path.join("serving", "disagg.py"),
                os.path.join("serving", "fleet.py")):
        assert rel in handoff_files, (
            f"engine/kv_handoff call site missing from {rel}: "
            f"{handoff_files}")


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))


# -- device scopes --------------------------------------------------------------
# ``jax.named_scope`` names: what the profile of a device trace groups by
# (benchmark/lib/scope_time.py, the kernels' rules, a builder reading a
# trace by hand). The same contract as the host spans above: a new scope is
# added here on purpose, a rename fails here first.
DEVICE_SCOPE_CATALOG = frozenset({
    "dlti_flash_attention_fwd", "dlti_flash_attention_bwd_dq",
    "dlti_flash_attention_bwd_dkv",
    "dlti_paged_attention_decode", "dlti_latent_attention_decode",
    "dlti_grouped_experts",
    "dlti_attn_window", "dlti_attn_full", "dlti_attn_over_cache",
    "dlti_mla_absorb", "dlti_mla_expand",
    # A latent-family prefill call's attention where its own tokens go
    # through the flash forward kernel: the expand, the kernel call, the
    # loop over what earlier calls wrote and the merge (PR 51).
    "dlti_mla_prefill_attn",
    "dlti_moe_routed", "dlti_moe_shared", "dlti_mamba2",
    "dlti_mhc_map", "dlti_mhc_mix",
    # One pass of a looped stack (``ut_steps`` > 1), the body the model
    # scans: the loop index is traced, so the passes share the one scope
    # and a profile shows it ``ut_steps`` times a step (PR 49).
    "dlti_loop_pass_u",
    # The decoder-hybrid-decoder family's mixers (models.sambay, PR 53): a
    # Mamba-1 layer, the differential combine after the attention kernel
    # (subtraction, head norm, 1 - lam0), a gated memory unit, and a
    # cross-attention layer over the shared pool (its kernel call and its
    # combine inside it).
    "dlti_mamba1", "dlti_diff_attention", "dlti_gmu", "dlti_cross_attention",
    # The Mamba-1 training scan and its own backward pass (models.mamba1,
    # PR 56): the Pallas kernels' names on the TPU, the scopes round XLA's
    # loops elsewhere; what the ``ssm_scan_*`` readers sum.
    "dlti_selective_scan_fwd", "dlti_selective_scan_bwd",
})


def _scope_literals():
    found = set()
    for root, _dirs, files in os.walk(PKG):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(root, name)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "named_scope"):
                    found.update(
                        c.value for arg in node.args for c in ast.walk(arg)
                        if isinstance(c, ast.Constant)
                        and isinstance(c.value, str)
                        and c.value.startswith("dlti_"))
    return found


def test_every_device_scope_name_is_pinned():
    found = _scope_literals()
    assert "dlti_loop_pass_u" in found and "dlti_attn_full" in found
    assert {"dlti_mamba1", "dlti_diff_attention", "dlti_gmu",
            "dlti_cross_attention"} <= found
    assert found == DEVICE_SCOPE_CATALOG, (
        sorted(found - DEVICE_SCOPE_CATALOG),
        sorted(DEVICE_SCOPE_CATALOG - found))
