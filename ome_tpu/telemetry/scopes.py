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
#   moe_router, moe_experts, moe_shared   under `mlp`: all an expert
#               layer decides from its router's input (`moe_decide`:
#               logits, top-k, weights, the sort of pairs by expert,
#               the held range's mask, the group sizes), AHEAD of
#               `qkv` where the router reads the layer's input
#               (`router_pre_attn`); the routed experts' gather,
#               grouped matmuls and combine; the shared expert and its
#               gate (models/llama.py)
#   attn_window, attn_global   a periodic window / global model's
#               layer kinds, each AROUND a layer's `kv_write` and
#               `attn` inside `layers`: the ring's row written and the
#               rows a slot holds read; a full-length row written and
#               every row read (models/llama.py `_mha`)
SUBPHASES = ("gdn_mixer", "gdn_state", "moe_router", "moe_experts",
             "moe_shared", "attn_window", "attn_global", "attn_latent")
# `name=` of Pallas kernels written after KERNELS was copied (below)
SUBKERNELS = ("latent_decode", "latent_prefill")

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


# -- start-up and compilation (metric label values, not capture names) --
# Appended below `scoped` on purpose: its line numbers are part of
# every traced operation's location, and with metadata in the compile
# cache's key (engine/serve.py) a moved line is a new key.

# ome_engine_startup_phase_seconds{phase=...} / ome_router_...: the
# phases of a process's start, in order; defined where the router,
# which never imports JAX, can reach them
from .startup import STARTUP_PHASES  # noqa: E402,F401

# ome_engine_compile_seconds_total{stage=...}: where compiling a
# program spends its time, as JAX's monitoring events report it
# (perf/ledger.py books them, by program, on /debug/programs too)
#   trace            jaxpr_trace_duration of the outermost traced
#                    function (a jitted function called inside a
#                    traced body reports too; its time is in the outer)
#   lower            jaxpr_to_mlir_module_duration: jaxpr -> StableHLO
#   backend_compile  backend_compile_duration less the cache retrieval
#                    inside it: XLA's compile on a miss; on a hit what
#                    is left is the hashing of the cache key
#   cache_load       cache_retrieval_time_sec: an executable read from
#                    the persistent cache and loaded
#   introspect       the wall time of ProgramLedger._build_entry at a
#                    program's first dispatch LESS what JAX reported
#                    from inside it (that is the program's one trace,
#                    lowering and compile, booked above: the dispatch
#                    reuses them): the cost analyses, the compiled
#                    text, the pass over its instructions
# Disjoint on a thread, so they add up to seconds of work.
COMPILE_STAGES = ("trace", "lower", "backend_compile", "cache_load",
                  "introspect")
# {when=...}: before `server.start()` returned, or after
COMPILE_WHEN = ("startup", "serving")
# ome_engine_compile_events_total{outcome=...}: a persistent-cache
# entry read, or one written after a compile
COMPILE_OUTCOMES = ("cache_hit", "cache_miss")
# flight event of one compile stage of one program, once the scheduler
# holds the ledger: {program, stage, seconds, cache}
PROGRAM_COMPILED = "program_compiled"


# -- latent attention (PR 46; described here, below `scoped`, for the
# reason above) --
#   attn_latent   (SUBPHASES) a latent-attention (MLA) layer, AROUND
#               its `kv_write` and `attn` inside `layers`, as
#               `attn_global` is: the slot's one row `[c | k_pe]`
#               written into the stacked slab in place, and the kernel
#               over the rows the slot holds (a prompt: over its
#               materialised heads). The projections, the absorbed
#               query and a prompt's materialised keys and values sit
#               under `qkv`, `w_uv`'s lift and `wo` under `o_proj`
#               (models/mla.py)
#   latent_decode, latent_prefill   (SUBKERNELS) the two kernels'
#               `name=` (ops/flash.py): a decode step's absorbed
#               queries over a slot's cached rows, each block read once
#               as key and as value; a prompt's causal blocked
#               attention at query / key width nope + rope and value
#               width v_head_dim. The benchmark reads all three from
#               benchmark/latent_kinds.py
