"""The names the program gives itself in a device trace: ONE list.

A profiler trace is read by name. These are the names the program
promises to keep, so a reduction written against them (a benchmark
metric, ``benchmark/scopes.py``) survives a refactor of the code that
carries them; ``tests/test_trace_names.py`` holds every one of them to
the compiled programs.

Four kinds, each arriving in the trace its own way (docs/observability.md
§device-trace-names says in which field of the xplane):

- **annotations**: every span a :class:`~.spans.SpanRecorder` records is
  also a ``jax.profiler.TraceAnnotation`` named ``dtf:<span name>`` in
  the host plane, on the device planes' clock;
- **programs**: the jitted functions' names (``jit_<name>`` on the
  ``XLA Modules`` line);
- **scopes**: ``jax.named_scope`` sections of the model and the step —
  few and flat; where two nest (a paged prefill gathers the pool inside
  its attention) the innermost names the operation. JAX itself prefixes
  ``transpose(jvp(<scope>))`` on backward operations and
  ``rematted_computation/<scope>`` on recomputed ones, so forward,
  backward and recompute stay apart, and the collectives GSPMD inserts
  inherit the scope of the product they follow;
- **kernels**: ``pl.pallas_call(name=...)``.

Strings only, jax-free like the rest of the package.
"""

from __future__ import annotations

import re

ANNOTATION_PREFIX = "dtf:"

# -- span names (annotation = ANNOTATION_PREFIX + name) ---------------------
SPAN_DECODE_CHUNK = "decode_chunk"
SPAN_PREFILL = "prefill"
SPAN_SPEC_VERIFY = "spec_verify"
SPAN_LM_EPOCH_SCAN = "lm_epoch_scan"
SPAN_LM_COMPILED_RUN = "lm_compiled_run"
SPAN_EPOCH_SCAN = "epoch_scan"
SPAN_COMPILED_RUN = "compiled_run"
SPAN_CHECKPOINT_SAVE = "checkpoint_save"
# Set-up (PR 37), category ``setup``: kept in the process's set-up record
# (observability/setup.py) beside the compile phases inside them.
SPAN_LM_MODEL_INIT = "lm_model_init"
SPAN_LM_INIT_STATE = "lm_init_state"
SPAN_SERVER_INIT = "server_init"
SETUP_SPANS = (SPAN_LM_MODEL_INIT, SPAN_LM_INIT_STATE, SPAN_SERVER_INIT)

# -- jitted programs (the function's __name__; "jit_" + it in the trace) ----
PROGRAM_EPOCH = "epoch"
PROGRAM_RUN = "run"
PROGRAM_CHUNK = "_chunk_graph"
PROGRAM_PREFILL = "_prefill_graph"
PROGRAM_PAGED_PREFILL = "_paged_prefill_graph"
PROGRAM_VERIFY = "_verify_graph"
PROGRAMS = (
    PROGRAM_EPOCH, PROGRAM_RUN, PROGRAM_CHUNK, PROGRAM_PREFILL,
    PROGRAM_PAGED_PREFILL, PROGRAM_VERIFY,
)

# -- scopes ------------------------------------------------------------------
EMBED = "embed"
ATTN_QKV = "attn_qkv"
ATTN_CORE = "attn_core"
ATTN_OUT = "attn_out"
MLP = "mlp"
LM_HEAD = "lm_head"
LOSS = "loss"
KV_WRITE = "kv_write"
KV_GATHER = "kv_gather"
KV_RESTACK = "kv_restack"
PICK = "pick"
OPTIMIZER = "optimizer"
MODEL_SCOPES = (EMBED, ATTN_QKV, ATTN_CORE, ATTN_OUT, MLP, LM_HEAD)
# The hybrid stack's other mixers (models/hybrid.py). A state-space layer:
# its projections in and out with the gated norm between, the causal
# depthwise convolution, the chunked scan. An expert layer: the router, the
# gather of routed rows and the weighted return, the routed experts'
# products, the shared expert.
SSM_PROJ = "ssm_proj"
SSM_CONV = "ssm_conv"
SSM_SCAN = "ssm_scan"
MOE_ROUTE = "moe_route"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_SHARED = "moe_shared"
# A gated delta-rule layer ("K"): its projections (in, out, both low-rank
# gates and the write strength), its causal depthwise convolutions, its
# decay and normalisation arithmetic (L2 norms, log-decay, the gated output
# norm), the chunked scan. Latent attention ("L") runs under the attention
# scopes, the dense gated feed-forward ("D") under MLP.
KDA_PROJ = "kda_proj"
KDA_CONV = "kda_conv"
KDA_GATE = "kda_gate"
KDA_SCAN = "kda_scan"
# A gated delta-rule layer with one decay per head ("N", Gated DeltaNet):
# its projections in and out with the gated output norm, the causal
# depthwise convolution (served: with the shift of the slot's last rows),
# the chunked scan over a sequence (prefill, training) and the one-token
# update of a slot's state (the served decode step); the L2 norms, the
# decay and the write strength run under the scan's or the update's scope.
GDN_PROJ = "gdn_proj"
GDN_CONV = "gdn_conv"
GDN_SCAN = "gdn_scan"
GDN_STATE = "gdn_state"
HYBRID_SCOPES = (
    SSM_PROJ, SSM_CONV, SSM_SCAN, MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS,
    MOE_SHARED,
)
KDA_SCOPES = (KDA_PROJ, KDA_CONV, KDA_GATE, KDA_SCAN)
# The hybrid stack's two gated attention kinds: the core of a full layer
# ("G": the softmax over the block pool, its reads of the pool included)
# and of a window layer ("W": the softmax over the slot's ring).
ATTN_FULL = "attn_full"
ATTN_WINDOW = "attn_window"
RING_SCOPES = (ATTN_FULL, ATTN_WINDOW)
GDN_SCOPES = (GDN_PROJ, GDN_CONV, GDN_SCAN, GDN_STATE)
SCOPES = MODEL_SCOPES + (
    LOSS, KV_WRITE, KV_GATHER, KV_RESTACK, PICK, OPTIMIZER,
) + HYBRID_SCOPES + KDA_SCOPES + RING_SCOPES + GDN_SCOPES

# -- Pallas kernels -----------------------------------------------------------
KERNEL_FLASH_FWD = "flash_fwd"
KERNEL_FLASH_BWD_FUSED = "flash_bwd_fused"
KERNEL_FLASH_BWD_DQ = "flash_bwd_dq"
KERNEL_FLASH_BWD_DKV = "flash_bwd_dkv"
KERNEL_MLP_TRAIN_STEP = "mlp_train_step"
KERNEL_MLP_TRAIN_EPOCH = "mlp_train_epoch"
# The delta rule's decayed scores inside a chunk (ops/kda.py).
KERNEL_KDA_SCORES_FWD = "kda_scores_fwd"
KERNEL_KDA_SCORES_BWD = "kda_scores_bwd"
KERNELS = (
    KERNEL_FLASH_FWD, KERNEL_FLASH_BWD_FUSED, KERNEL_FLASH_BWD_DQ,
    KERNEL_FLASH_BWD_DKV, KERNEL_MLP_TRAIN_STEP, KERNEL_MLP_TRAIN_EPOCH,
    KERNEL_KDA_SCORES_FWD, KERNEL_KDA_SCORES_BWD,
)


# -- reading a scope back ------------------------------------------------------
PHASES = ("forward", "backward", "recompute")
_JAX_WRAPPER = re.compile(r"(?:transpose|jvp|vmap)\((.*)\)")


def scope_of(op_name: str) -> tuple[str | None, str]:
    """``(scope, phase)`` of an operation's ``op_name`` (the ``tf_op`` of
    a trace, the ``op_name`` metadata of compiled HLO): the innermost
    scope on its path, None where there is none; ``rematted_computation``
    on the path makes the phase recompute, ``transpose(`` backward. JAX's
    own wrappers round a scope are looked through
    (``transpose(jvp(attn_core))``); ``jit(loss)`` is a program, not the
    scope ``loss``."""
    parts = op_name.split("/")
    phase = "forward"
    if "rematted_computation" in parts:
        phase = "recompute"
    elif any(p.startswith("transpose(") for p in parts):
        phase = "backward"
    for part in reversed(parts):
        while (m := _JAX_WRAPPER.fullmatch(part)):
            part = m.group(1)
        if part in SCOPES:
            return part, phase
    return None, phase
