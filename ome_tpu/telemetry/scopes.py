"""The names the program writes into a profiler capture: one
vocabulary, defined here and nowhere else in the program.

Three kinds of name reach a `POST /debug/profile` capture
(docs/tracing-timeline.md):

  * scopes (`jax.named_scope`) become the `op_name` path of every HLO
    operation traced under them: `jit(_decode_paged)/decode/layers/
    while/body/closed_call/mlp/dot_general`. The root scope of a
    jitted program body is its FAMILY, the scopes inside
    `models/llama.py` and around sampling are PHASES;
  * a Pallas kernel's `name=` becomes the name of its custom-call
    instruction (`%paged_attention.3`), which is what the trace's
    `XLA Ops` line prints (KERNELS);
  * `jax.profiler.TraceAnnotation` spans of the scheduler
    (`sched.<phase>`) and of the admission thread (`admit.prefill`)
    land on the host plane of the same capture, on its clock.

All three are metadata: no operation is added, moved or fused
differently, and an annotation outside a capture costs one atomic
load. JAX's persistent compilation cache leaves metadata out of its
key by default, so a program whose operations did not change would
keep the names it was first compiled with; `engine/serve.py` puts
metadata into the key for the serving process.

A device trace names an operation by its instruction and carries no
metadata: the program's ledger reads each instruction's scope path
out of the compiled text and serves it with `/debug/programs`
(`perf/ledger.py: instruction_paths`).

The benchmark keeps its own copy of this vocabulary
(benchmark/phases.py) and never imports this module.
"""

from __future__ import annotations

import functools

import jax

# root scope of each jitted program body in engine/core.py; helper
# programs (mask-row set, dtype converts between steps) get none
FAMILIES = ("decode", "prefill", "verify", "insert")

# inside llama.forward / forward_paged and at the sampling call
# sites; the five after `layers` sit inside the layer scan's body
PHASES = ("embed", "layers", "qkv", "kv_write", "attn", "o_proj",
          "mlp", "lm_head", "sample")

# `name=` of every pl.pallas_call in ops/
KERNELS = ("paged_attention", "flash_decode", "flash_prefill",
           "int4_matmul")

# finer names, each written INSIDE one of the PHASES above, so a reader
# that knows only PHASES still books the time on the phase around it
# (the deepest name it knows). They are tuples of their own, not
# longer old ones: the benchmark holds its copy of PHASES and KERNELS
# equal to the two above (tests/benchmark/test_phases.py) and reads
# these from files of its own (benchmark/subphases.py).
#   gdn_mixer   a Gated DeltaNet layer's mixer whole: projections and
#               conv (under `qkv`), the recurrence (`attn`), the gated
#               norm and output projection (`o_proj`)
#   gdn_state   inside it, under `kv_write`: the recurrent state's
#               decay and rank-one update (models/gdn.py)
#   moe_router, moe_experts, moe_shared   under `mlp`: router logits,
#               top-k and the sort; the routed experts' gather,
#               grouped matmuls and scatter; the shared expert and its
#               gate (models/llama.py)
SUBPHASES = ("gdn_mixer", "gdn_state", "moe_router", "moe_experts",
             "moe_shared")
# `name=` of Pallas kernels written after KERNELS was copied: none yet
SUBKERNELS = ()

# ome_engine_step_phase_seconds{phase=...} label values, each also a
# `sched.<phase>` span (scheduler._phase)
SCHED_PHASES = ("plan", "mask_apply", "dispatch", "device_loop",
                "device_wait", "host_sample", "insert")
SCHED_PREFIX = "sched."
ADMIT_PREFILL = "admit.prefill"


def scoped(name: str):
    """Decorator: trace the function's body under `name`. Sits UNDER
    `jax.jit` so the jit keeps the function's own name (the trace's
    module names and the benchmark's `decode_module` depend on it)."""
    def deco(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return deco
