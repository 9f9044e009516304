"""`python -m ome_tpu.engine.serve` — the engine container entrypoint.

What the catalog's ServingRuntimes run (config/runtimes/ome/*.yaml):
loads a staged model directory (config.json + safetensors via
models/checkpoint.py + tokenizer), builds the compiled
InferenceEngine + continuous-batching Scheduler, and serves the
OpenAI-compatible HTTP surface (engine/server.py). Mirrors the role
of the reference runtimes' `python -m sglang.launch_server` /
`vllm serve` commands (SURVEY.md L0) but with the in-repo JAX engine.

`--random-weights` skips checkpoint loading (hermetic tests, dry
runs); `--task embed` serves /v1/embeddings through the stateless
EmbeddingEngine (engine/embed.py) instead of the generation stack.
"""

from __future__ import annotations

import argparse
import contextlib
import logging
import sys
import threading
import time
from typing import Optional

from ..telemetry.startup import StartupTimeline

log = logging.getLogger("ome.engine.serve")

# this process's start-up phases; `main` makes it, first thing
_startup: Optional[StartupTimeline] = None


def _phase(name: str):
    """One start-up phase (telemetry/scopes.py STARTUP_PHASES), the
    start-up's counterpart of `Scheduler._phase`: it runs from the end
    of the phase before it to the end of the `with` block, so `weights`,
    which `load_engine` closes, takes its part out of the `engine` block
    around it. Outside `main` (a test that calls `load_engine`) it is
    nothing."""
    if _startup is None:
        return contextlib.nullcontext()
    return _startup.phase(name)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ome-engine", description="OME-TPU serving engine")
    p.add_argument("--model-dir", required=True,
                   help="staged model directory (config.json + safetensors)")
    p.add_argument("--model-name", default=None,
                   help="name reported by /v1/models (default: dir name)")
    p.add_argument("--max-slots", type=int, default=16,
                   help="decode batch width (continuous-batching slots)")
    p.add_argument("--max-seq", type=int, default=None,
                   help="KV capacity per slot (default: model max)")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--task", choices=("generate", "embed"),
                   default="generate")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--random-weights", action="store_true",
                   help="random init instead of loading safetensors "
                        "(tests / dry runs); config.json's "
                        "initializer_range is the deviation, 0.02 "
                        "without it")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel size over the local mesh")
    p.add_argument("--quantization",
                   choices=("none", "int8", "int4", "fp8"),
                   default="none",
                   help="weight-only quantization at load time (int8 "
                        "halves decode HBM traffic; int4 groupwise "
                        "quarters it; fp8 = float8_e4m3 per-channel, "
                        "v6e-targeted)")
    p.add_argument("--adapter", action="append", default=None,
                   help="LoRA serving (FineTunedWeight): a bare PEFT "
                        "dir merges into the base weights at load; "
                        "repeatable name=dir pairs serve MULTIPLE "
                        "adapters concurrently (per-request routing "
                        "by model id, hot add via POST /v1/adapters)")
    p.add_argument("--lora-slots", type=int, default=None,
                   help="preallocated hot-swappable LoRA adapter "
                        "slots (default: number of name=dir adapters, "
                        "min 4 when any are given)")
    p.add_argument("--lora-rank", type=int, default=16,
                   help="max adapter rank a LoRA slot holds")
    p.add_argument("--prefix-cache-mb", type=int, default=None,
                   help="HBM byte budget (MiB) for the radix prompt-"
                        "prefix KV cache (0 disables); prompts sharing "
                        "cached leading token blocks prefill only "
                        "their suffix (default 256; 0 for a model "
                        "whose slots own recurrent state or a window "
                        "ring, which no cached prefix can seed)")
    p.add_argument("--kv-block", type=int, default=0,
                   help="paged KV cache block size in tokens (0 = "
                        "dense per-slot cache); pool-allocated HBM "
                        "sized by tokens in flight, not "
                        "slots x max-seq (GQA models)")
    p.add_argument("--kv-blocks", type=int, default=None,
                   help="paged KV pool size in blocks (default: "
                        "dense-equivalent capacity)")
    p.add_argument("--kv-dtype", choices=("bf16", "int8"),
                   default="bf16",
                   help="paged KV block pool storage dtype: int8 "
                        "halves pool HBM per cached token (per-row-"
                        "per-head scales, quantize on append, "
                        "dequantize in the attention kernel) so the "
                        "same budget holds ~2x the sequences "
                        "(docs/kv-hierarchy.md); needs --kv-block")
    p.add_argument("--prefix-cache-host-mb", type=int, default=0,
                   help="host-DRAM byte budget (MiB) for the prefix-"
                        "cache spill tier (0 disables): evicted radix "
                        "blocks spill to host instead of being "
                        "dropped and swap back in asynchronously on "
                        "the next hit — never blocking the step path")
    p.add_argument("--control-port", type=int, default=None,
                   help="leader->follower op-replication port for "
                        "multi-host serving (default: engine/multihost "
                        "CONTROL_PORT)")
    p.add_argument("--disaggregation-mode",
                   choices=("none", "prefill", "decode"), default="none",
                   help="PD-disaggregated serving role: 'prefill' "
                        "exports KV over /pd/prefill; 'decode' fetches "
                        "KV from --prefill-peer instead of computing "
                        "prefill locally")
    p.add_argument("--prefill-peer", default=None,
                   help="single prefill peer URL (back-compat alias "
                        "for --prefill-url; merged first into the "
                        "pool)")
    p.add_argument("--prefill-url", action="append", default=None,
                   metavar="URL",
                   help="prefill pool peer URL; repeatable. A decode "
                        "node tracks per-peer health (the router's "
                        "breaker/draining discipline) and fails a "
                        "dropped /pd/prefill fetch over to the next "
                        "healthy peer (docs/pd-disaggregation.md). At "
                        "least one of --prefill-url/--prefill-peer is "
                        "required for --disaggregation-mode decode")
    p.add_argument("--pd-local-fallback", action="store_true",
                   help="decode role: when every prefill peer is out "
                        "of rotation, compute the prefill locally "
                        "instead of failing the request (costs decode-"
                        "node FLOPs; keeps availability)")
    p.add_argument("--pd-attempt-timeout", type=float, default=120.0,
                   metavar="SECONDS",
                   help="per-attempt /pd/prefill fetch timeout; each "
                        "attempt is further capped by the request's "
                        "own deadline")
    p.add_argument("--max-restarts", type=int, default=3,
                   help="consecutive engine-fault recovery attempts "
                        "before the scheduler goes permanently dead "
                        "(/health 503); 0 = first fault is fatal")
    p.add_argument("--max-queue-wait", type=float, default=30.0,
                   help="reject new requests (429 + Retry-After) when "
                        "the estimated pending-queue wait exceeds "
                        "this many seconds")
    p.add_argument("--class-weights", default=None, metavar="SPEC",
                   help="weighted-fair scheduling weights per priority "
                        "class (docs/multi-tenancy.md), e.g. "
                        "'interactive=8,standard=4,batch=1'; partial "
                        "specs keep defaults, and every class keeps "
                        "weight >= 1 so none can be starved by config")
    p.add_argument("--class-wait-cap", action="append", default=None,
                   metavar="CLASS=SECONDS",
                   help="per-class queue-wait admission cap in seconds "
                        "(repeatable); defaults derive from "
                        "--max-queue-wait (interactive 0.25x, "
                        "standard 1x, batch 4x) so a batch flood "
                        "sheds batch traffic first")
    p.add_argument("--no-priority-scheduling", action="store_true",
                   help="disable per-class queues, weighted-fair slot "
                        "allocation and class-ranked preemption: all "
                        "requests schedule FIFO as one class (classes "
                        "are still parsed and recorded in logs)")
    p.add_argument("--pipeline-depth", type=int, default=1,
                   help="decode steps dispatched ahead of token "
                        "emission: 1 overlaps the host-side token "
                        "fetch/finish bookkeeping with the next "
                        "device step (one-step emission lag), 0 "
                        "restores the synchronous fetch-every-step "
                        "loop; structured-output batches stay "
                        "pipelined through forced-token grammar runs "
                        "(docs/step-plan.md)")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="decode iterations fused into one device "
                        "program (docs/multi-step-decode.md): the "
                        "host dispatches and syncs once per K-token "
                        "chunk instead of per token; greedy output "
                        "is byte-identical to K=1. Composes with "
                        "masked, speculative, pipelined, and "
                        "multi-host serving (docs/step-plan.md); "
                        "engines without the decode_multi op clamp "
                        "to 1, counted in "
                        "ome_engine_step_degradations_total")
    p.add_argument("--spec-tokens", type=int, default=0,
                   help="speculative decoding: max draft tokens per "
                        "slot per step proposed by the host-side "
                        "n-gram drafter and verified in one batched "
                        "multi-token forward "
                        "(docs/speculative-decoding.md); 0 = off "
                        "(default). Greedy output is byte-identical "
                        "either way; composes with multi-token "
                        "chunks, pipelining, and multi-host serving "
                        "(docs/step-plan.md)")
    p.add_argument("--journal", default=None, metavar="DIR",
                   help="durable requests (docs/durability.md): "
                        "append-only JSONL request journal in DIR; "
                        "admitted requests and their generated tokens "
                        "are journaled, and on restart unfinished "
                        "requests resume byte-identical (greedy) to "
                        "an uninterrupted run")
    p.add_argument("--journal-fsync",
                   choices=("always", "batch", "off"), default="batch",
                   help="journal durability: 'always' fsyncs every "
                        "append, 'batch' (default) fsyncs at most "
                        "every ~100ms from the scheduler loop, 'off' "
                        "leaves flushing to the OS")
    p.add_argument("--journal-compact-mb", type=int, default=4,
                   help="rewrite the journal (dropping tombstoned "
                        "entries, consolidating progress) when it "
                        "exceeds this many MiB")
    p.add_argument("--drain-grace", type=float, default=30.0,
                   help="graceful-drain window after SIGTERM: /ready "
                        "flips 503 and new work is rejected while "
                        "in-flight requests get this many seconds to "
                        "finish; leftovers are journaled (with "
                        "--journal) and evicted with finish_reason="
                        "shutdown. A second SIGTERM/SIGINT forces "
                        "immediate shutdown")
    p.add_argument("--faults", default=None,
                   help="deterministic fault-injection spec "
                        "(ome_tpu/faults.py grammar, e.g. "
                        "'engine_step.raise@100'); also via OME_FAULTS")
    p.add_argument("--request-log", default=None,
                   help="JSONL request-log path: one record per "
                        "request with trace id, queue-wait/TTFT/TPOT, "
                        "tokens, finish_reason (docs/observability.md)")
    p.add_argument("--profile-dir", default=None,
                   help="enable POST /debug/profile?seconds=N: "
                        "on-demand jax.profiler captures into this "
                        "directory (no-op off-TPU; off when unset)")
    p.add_argument("--span-log", default=None, metavar="PATH",
                   help="span-timeline JSONL path: one record per "
                        "finished phase span (queue, prefill, decode "
                        "chunks, spec verify, drain ...) joinable "
                        "across processes by trace id and merged into "
                        "a Perfetto timeline by "
                        "scripts/trace_export.py "
                        "(docs/tracing-timeline.md)")
    p.add_argument("--debug-endpoints", action="store_true",
                   help="enable GET /debug/events (flight-recorder "
                        "ring), GET /debug/state (scheduler "
                        "snapshot) and GET /debug/programs (program "
                        "cost ledger); 403 when off — these expose "
                        "request ids and internals, keep them off "
                        "public listeners")
    p.add_argument("--ledger-mode", default="auto",
                   choices=("auto", "full", "model", "off"),
                   help="program cost ledger (docs/perf-attribution"
                        ".md): auto = XLA cost introspection on TPU, "
                        "analytic byte model elsewhere; full/model "
                        "force a path; off disables capture")
    p.add_argument("--flight-events", type=int, default=2048,
                   metavar="N",
                   help="flight-recorder ring capacity: the last N "
                        "scheduler lifecycle events kept in memory "
                        "for /debug/events and crash dumps")
    p.add_argument("--flight-dump-dir", default=None, metavar="DIR",
                   help="auto-dump the flight-recorder ring into DIR "
                        "as flight-<pid>-<n>.json on engine-fault "
                        "recovery and permanent death (the chaos "
                        "harness reads these into violation bundles)")
    return p


def _load_params_cfg(args, dtype, mesh=None):
    """Shared load path: checkpoint (or random init) + LoRA merge.

    Returns a NUMPY param tree for the checkpoint path — device
    placement is the caller's job (single-device asarray, or
    shard_params for tp>1 so the full tree never lands on one chip).
    Random weights are made on the device; with `mesh` (tp>1) they
    are born sharded for the same reason, and are the same values
    (the seed and the partitionable threefry decide them, not the
    layout).
    """
    import jax

    from ..models import checkpoint, llama
    from ..models.config import ModelConfig

    if args.random_weights:
        import json
        import os
        cfg_path = os.path.join(args.model_dir, "config.json")
        if os.path.exists(cfg_path):
            with open(cfg_path) as f:
                hf = json.load(f)
            # as `load_params` does for a checkpoint: a NAMED
            # architecture that models/llama.py does not implement
            # would be served as a dense llama under its name
            archs = checkpoint.unsupported_architectures(hf)
            if archs:
                raise SystemExit(
                    f"--random-weights: architecture {archs} is not "
                    "implemented by models/llama.py (supported: "
                    f"{sorted(checkpoint.SUPPORTED_ARCHITECTURES)})")
            cfg = ModelConfig.from_hf_config(hf)
        else:
            from ..models.config import tiny_test
            cfg = tiny_test()
        cfg = cfg.replace(dtype=dtype)

        def init():
            return llama.init_params(jax.random.PRNGKey(0), cfg)

        # one program for the whole tree: run leaf by leaf, a 4 B
        # model took 91 s to initialise on the chip and kept float32
        # copies of its largest leaves beside them
        shardings = None
        if mesh is not None:
            from ..parallel.sharding import param_shardings
            shardings = param_shardings(jax.eval_shape(init), mesh)
        params = jax.jit(init, out_shardings=shardings)()
        log.info("initialized random weights: %.2fM params",
                 llama.param_count(params) / 1e6)
        return params, cfg
    params, cfg = checkpoint.load_params(args.model_dir, dtype=dtype,
                                         device_put=False)
    merge_dir = _adapter_args(args)[0]
    if merge_dir:
        from ..models.lora import merge_lora
        merged = merge_lora(params, cfg, merge_dir)
        log.info("merged %d LoRA deltas from %s", merged, merge_dir)
    log.info("loaded checkpoint from %s", args.model_dir)
    return params, cfg


def _adapter_args(args):
    """--adapter forms -> (merge_dir | None, {name: dir}).

    A single bare directory keeps the legacy merge-at-load behavior
    (one adapter at full base speed); any name=dir entry switches to
    multi-LoRA serving slots."""
    entries = args.adapter or []
    named = {}
    bare = []
    for e in entries:
        if "=" in e:
            name, _, path = e.partition("=")
            named[name] = path
        else:
            bare.append(e)
    if bare and (named or len(bare) > 1):
        raise SystemExit("--adapter: use name=dir form when serving "
                         "multiple adapters")
    return (bare[0] if bare else None), named


def _refuse_for_slot_state(args) -> None:
    """Stop, before any weight is loaded and with the reasons, where
    the model's config.json describes a model whose slots own state
    that is not full-length KV rows (a hybrid model's recurrent
    state, a window layer's ring: core.SLOT_STATE_REFUSALS) and the
    command line asks for something that takes such rows to be all a
    slot owns. A prefix cache that was not asked for is settled here:
    off for such a model (said in the log), 256 MiB for any other."""
    import json
    import os

    from ..models.config import ModelConfig
    from .core import (SLOT_STATE_KINDS, slot_state_kind,
                       slot_state_refusals)
    path = os.path.join(args.model_dir, "config.json")
    kind = None
    if os.path.exists(path):
        with open(path) as f:
            cfg = ModelConfig.from_hf_config(json.load(f))
        kind = slot_state_kind(cfg)
    if args.prefix_cache_mb is None:
        args.prefix_cache_mb = 0 if kind else 256
        if kind:
            log.info("prefix cache off (no --prefix-cache-mb given): "
                     "%s", SLOT_STATE_KINDS[kind])
    if kind is None:
        return
    _, named = _adapter_args(args)
    refused = slot_state_refusals(
        cfg, kv_block=args.kv_block or args.kv_blocks,
        prefix_cache=args.prefix_cache_mb
        or getattr(args, "prefix_cache_host_mb", 0),
        lora=named or args.lora_slots, tp=args.tp > 1,
        spec_tokens=getattr(args, "spec_tokens", 0),
        pd=getattr(args, "disaggregation_mode", "none") != "none",
        journal=getattr(args, "journal", None))
    if refused:
        raise SystemExit(
            SLOT_STATE_KINDS[kind] + "; refused:\n  "
            + "\n  ".join(refused))


def load_engine(args, dist=None, ledger=None):
    import jax.numpy as jnp

    from ..perf.ledger import ProgramLedger
    from .core import InferenceEngine

    if ledger is None:
        ledger = ProgramLedger(mode=getattr(args, "ledger_mode", "auto"))
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if dist is not None and args.tp <= 1:
        # multi-host slice: tp spans every chip of every host by
        # default (the LWS north-star layout, e.g. v5e-16 = 4x4)
        import jax
        args.tp = jax.device_count()
        log.info("multi-host: tp=%d over %d processes", args.tp,
                 dist.num_processes)
    _refuse_for_slot_state(args)
    mesh = None
    if args.tp > 1:
        from ..parallel.mesh import MeshConfig, build_mesh
        mesh = build_mesh(MeshConfig(tp=args.tp))
    with _phase("weights"):
        params, cfg = _load_params_cfg(args, dtype, mesh)
        if cfg.is_moe and args.tp == 1:
            # single-device serving uses the ragged grouped-GEMM
            # dispatch; tp>1 keeps the dense path (shardable through
            # plain GSPMD)
            cfg = cfg.replace(moe_impl="ragged")
        if args.quantization in ("int8", "int4", "fp8"):
            from ..models.quant import quantize_params
            params = quantize_params(params, mode=args.quantization)
            log.info("quantized weights to %s (weight-only)",
                     args.quantization)
        if args.tp <= 1:
            import jax
            params = jax.tree.map(jnp.asarray, params)  # one transfer
    max_seq = args.max_seq or min(cfg.max_seq_len, 8192)
    _, named_adapters = _adapter_args(args)
    lora_slots = args.lora_slots if args.lora_slots is not None else \
        (max(4, len(named_adapters)) if named_adapters else 0)
    if args.tp > 1:
        if lora_slots:
            raise SystemExit("multi-LoRA serving is single-host tp=1 "
                             "for now (adapter stacks are unsharded); "
                             "use a merged --adapter dir with tp>1")
        if args.kv_block or args.kv_blocks:
            # refuse loudly rather than silently serving a dense cache
            # the operator sized a paged pool for
            raise SystemExit("--kv-block/--kv-blocks (paged KV) is "
                             "single-host tp=1 for now (the sharded "
                             "engine keeps the dense per-slot cache); "
                             "drop the flags with tp>1")
        if getattr(args, "kv_dtype", "bf16") == "int8":
            raise SystemExit("--kv-dtype int8 quantizes the paged "
                             "block pool, which is single-host tp=1 "
                             "for now; drop the flag with tp>1")
        if getattr(args, "prefix_cache_host_mb", 0):
            raise SystemExit("--prefix-cache-host-mb (host-DRAM "
                             "prefix tier) is single-host tp=1 for "
                             "now; drop the flag with tp>1")
        # hand the host tree straight to shard_params: materializing it
        # on one device first would OOM exactly the models tp serves
        from .sharded import ShardedInferenceEngine
        return ShardedInferenceEngine(params, cfg, tp=args.tp,
                                      mesh=mesh,
                                      max_slots=args.max_slots,
                                      max_seq=max_seq,
                                      prefix_cache_bytes=args.prefix_cache_mb << 20,
                                      ledger=ledger)
    kv_dtype = getattr(args, "kv_dtype", "bf16")

    def build(kv_block, kv_blocks):
        return InferenceEngine(params, cfg, max_slots=args.max_slots,
                               max_seq=max_seq,
                               prefix_cache_bytes=args.prefix_cache_mb << 20,
                               prefix_host_bytes=getattr(
                                   args, "prefix_cache_host_mb", 0) << 20,
                               lora_slots=lora_slots,
                               lora_rank=args.lora_rank,
                               kv_block=kv_block,
                               kv_blocks=kv_blocks,
                               kv_dtype=(kv_dtype
                                         if kv_dtype != "bf16" else None),
                               ledger=ledger)
    try:
        engine = build(args.kv_block, args.kv_blocks)
    except ValueError as e:
        if not args.kv_block or "paged KV" not in str(e):
            raise
        # graceful degradation: an auto-selected runtime may pass
        # --kv-block for a model the paged coverage guard refuses
        # (MLA/MoE/sliding-window arch, or head_dim/heads outside the
        # Pallas kernel's envelope). Serving dense beats crash-looping
        # the pod — but shout, because the operator sized HBM for a
        # paged pool.
        log.warning("paged KV unavailable for this model (%s); "
                    "FALLING BACK to the dense per-slot cache — HBM "
                    "use is max-slots x max-seq, not tokens in flight",
                    e)
        if kv_dtype == "int8":
            # int8 storage rides the paged pool; the dense slab stays
            # at the model dtype, so the HBM halving is gone too
            log.warning("--kv-dtype int8 dropped with the paged pool")
            kv_dtype = "bf16"
        engine = build(0, None)
    for name, path in named_adapters.items():
        engine.register_adapter(name, path)
        log.info("registered LoRA adapter %r from %s", name, path)
    return engine


class _NullScheduler:
    """Placeholder driving nothing — embeddings are stateless."""

    healthy = True
    status = "ok"
    stats: dict = {}
    registry = None
    reject = "this deployment serves embeddings only"

    def start(self):
        pass

    def stop(self):
        pass

    def submit(self, req):
        raise RuntimeError(self.reject)

    def begin_drain(self):
        pass

    def drain_idle(self):
        return True  # stateless: nothing in flight to wait for


class DrainController:
    """SIGTERM/SIGINT choreography (docs/durability.md drain state
    machine): the FIRST signal begins a graceful drain — /ready flips
    503 (the router stops selecting this replica), new admissions are
    rejected 503 + Retry-After, in-flight and queued requests get up
    to `grace` seconds to finish; a SECOND signal (either kind)
    forces immediate shutdown. Either way the process exits 0 — with
    a journal, whatever did not finish is durably recorded and the
    replacement process resumes it."""

    def __init__(self, server, scheduler, grace: float = 30.0,
                 journal=None, poll_interval: float = 0.02):
        self.server = server
        self.scheduler = scheduler
        self.grace = grace
        self.journal = journal
        self.poll_interval = poll_interval
        self._signalled = threading.Event()
        self._force = threading.Event()
        self.drained: bool = False
        reg = getattr(scheduler, "registry", None)
        self._g_draining = reg.gauge(
            "ome_engine_draining",
            "1 while this replica is draining after SIGTERM") \
            if reg is not None else None
        self._g_duration = reg.gauge(
            "ome_engine_drain_duration_seconds",
            "Seconds the last (or current) drain has taken") \
            if reg is not None else None

    def install(self):
        """Install the signal handlers (main thread only — the
        interpreter requires it)."""
        import signal
        signal.signal(signal.SIGTERM, self.handle_signal)
        signal.signal(signal.SIGINT, self.handle_signal)

    def handle_signal(self, *_):
        if self._signalled.is_set():
            self._force.set()  # second signal: stop waiting
        else:
            self._signalled.set()

    def wait(self):
        """Block until the first signal, then run the drain."""
        self._signalled.wait()
        return self.drain()

    def drain(self) -> bool:
        """Run the drain window; returns True when every in-flight
        request finished inside the grace period."""
        from .. import faults
        t0 = time.monotonic()
        log.warning("shutdown signal: draining (grace %.1fs; signal "
                    "again to force)", self.grace)
        begin = getattr(self.server, "begin_drain", None)
        if begin is not None:
            begin()
        else:  # bare scheduler (tests without an HTTP front)
            sched_begin = getattr(self.scheduler, "begin_drain", None)
            if sched_begin is not None:
                sched_begin()
        if self._g_draining is not None:
            self._g_draining.set(1)
        drained = False
        idle = getattr(self.scheduler, "drain_idle", None)
        while time.monotonic() - t0 < self.grace:
            if self._force.is_set():
                log.warning("second signal: forcing shutdown with "
                            "work in flight")
                break
            if idle is not None and idle():
                drained = True
                break
            if self._g_duration is not None:
                self._g_duration.set(time.monotonic() - t0)
            time.sleep(self.poll_interval)
        if not drained and not self._force.is_set():
            # deterministic harness hook: lets tests pin the
            # drain-timeout eviction path
            faults.fire("drain_timeout")
        dur = time.monotonic() - t0
        if self._g_duration is not None:
            self._g_duration.set(dur)
        self._record_drain_span(t0, dur, drained)
        if drained:
            log.info("drain complete in %.2fs (all requests "
                     "finished)", dur)
        else:
            log.warning("drain window closed after %.2fs with work "
                        "in flight; evicting with finish_reason="
                        "shutdown%s", dur,
                        " (journaled for resume)"
                        if self.journal is not None else "")
        self.drained = drained
        return drained

    def _record_drain_span(self, t0: float, dur: float,
                           drained: bool) -> None:
        """Timeline + flight-recorder marks for the drain window (the
        scheduler's span_log/flight, when it has them)."""
        flight = getattr(self.scheduler, "flight", None)
        if flight is not None:
            flight.record("drain_end", drained=drained,
                          dur_s=round(dur, 3), forced=self._force.is_set())
        span_log = getattr(self.scheduler, "span_log", None)
        if span_log is None or not span_log.enabled:
            return
        from ..telemetry.tracing import Span
        ctx = getattr(self.scheduler, "_span_ctx", None)
        span = Span.begin("engine.drain", ctx=ctx, start_mono=t0,
                          start_wall=time.time() - dur)
        span.set(drained=drained, forced=self._force.is_set(),
                 grace_s=self.grace)
        span.end(t0 + dur)
        span_log.write(span)


class _PrefillNodeScheduler(_NullScheduler):
    """PD prefill nodes have no decode loop; /v1/* is rejected and the
    work arrives via /pd/prefill instead."""

    reject = ("this node serves PD prefill only (route completions to "
              "the decode pool)")

    def __init__(self, engine):
        self.engine = engine


def check_plan_preconditions(engine, args):
    """Validate explicitly requested composition features against the
    assembled engine stack BEFORE serving (docs/step-plan.md).

    The scheduler degrades gracefully at construction (counted in
    ome_engine_step_degradations_total), but an operator who asked
    for a feature on the command line gets a config error naming the
    failed plan precondition instead of a silently slower server.
    Returns an error string, or None when every requested feature can
    dispatch. Multi-host is NOT a refusal: ReplicatedEngine carries
    decode_multi / verify / commit_spec in the op vocabulary, so spec
    and multi-step compose with dist like everything else."""
    if args.spec_tokens > 0 and not callable(
            getattr(engine, "verify", None)):
        return ("--spec-tokens %d: plan precondition engine.verify "
                "unsatisfied — %s has no spec-verify op, so verify "
                "plans cannot dispatch (docs/step-plan.md); drop "
                "--spec-tokens or serve an engine with verify"
                % (args.spec_tokens, type(engine).__name__))
    if args.steps_per_dispatch > 1 and not (
            callable(getattr(engine, "decode_multi", None))
            and getattr(engine, "supports_multi_step", False)):
        return ("--steps-per-dispatch %d: plan precondition "
                "engine.decode_multi unsatisfied — %s has no "
                "multi-step decode op, so chunk plans cannot "
                "dispatch (docs/step-plan.md); drop "
                "--steps-per-dispatch or serve an engine with "
                "decode_multi"
                % (args.steps_per_dispatch, type(engine).__name__))
    return None


def load_embedder(args):
    import jax
    import jax.numpy as jnp

    from .embed import EmbeddingEngine
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    with _phase("weights"):
        params, cfg = _load_params_cfg(args, dtype)
        params = jax.tree.map(jnp.asarray, params)
    return EmbeddingEngine(params, cfg, max_seq=args.max_seq)


def main(argv=None) -> int:
    # the first statement: `interpreter` ends here
    global _startup
    _startup = StartupTimeline()
    with _phase("device"):
        logging.basicConfig(level=logging.INFO,
                            format="%(asctime)s %(name)s %(message)s")
        args = build_parser().parse_args(argv)
        from .. import device
        cache_dir = device.enable_compile_cache()
        # the server's programs carry names that a profiler capture is
        # read by (telemetry/scopes.py). JAX leaves metadata out of the
        # cache key by default: a program whose operations a release did
        # not change would then be loaded under the names it was first
        # compiled with. With metadata in the key a cache entry is found
        # again only from the same source tree at the same path.
        import jax
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        # compile and cache-load seconds, by stage and by program, from
        # here on: the ledger hooks JAX's monitoring events, which fire
        # where something compiles and nowhere else
        from ..perf.ledger import ProgramLedger
        ledger = ProgramLedger(mode=args.ledger_mode)
        ledger.listen()
        if args.faults:
            from .. import faults
            faults.install(args.faults)
            log.warning("fault injection ACTIVE: %s", args.faults)
        if _adapter_args(args)[0] and args.random_weights:
            log.error("--adapter merge requires a real checkpoint "
                      "(incompatible with --random-weights); name=dir "
                      "multi-LoRA slots work with either")
            return 2
        # parse the multi-tenancy flags up front so a bad spec fails fast
        # instead of after a multi-minute checkpoint load
        from ..priority import coerce_priority, parse_weight_spec
        class_weights = None
        class_wait_caps = None
        try:
            if args.class_weights:
                class_weights = parse_weight_spec(args.class_weights)
            if args.class_wait_cap:
                class_wait_caps = {}
                for spec in args.class_wait_cap:
                    cls, sep, secs = spec.partition("=")
                    if not sep:
                        raise ValueError(
                            f"bad --class-wait-cap {spec!r} "
                            "(expected class=seconds)")
                    class_wait_caps[coerce_priority(cls)] = float(secs)
        except ValueError as e:
            log.error("%s", e)
            return 2

        # join the cross-host rendezvous FIRST (before any jax call) when
        # the operator injected the LWS contract env (multinode.py:53-58)
        from . import multihost
        dist = multihost.init_from_env()
        control_port = args.control_port or multihost.CONTROL_PORT
        # named before any weight is loaded, so a launcher that expected
        # another platform can stop here
        dev = device.identity()
        log.info("device: platform=%s kind=%s count=%d (compile cache %s)",
                 dev["platform"], dev["kind"], dev["count"], cache_dir)

    with _phase("engine"):
        from .scheduler import Scheduler
        from .server import EngineServer
        from .tokenizer import load_tokenizer

        if dist is not None and args.task == "embed":
            # embeddings are stateless single-host programs; a multi-host
            # embed group would leave followers waiting on a control
            # channel the embed leader never opens
            log.error("--task embed does not support multi-host serving "
                      "(unset JAX_COORDINATOR_ADDRESS or use one process)")
            return 2
        prefill_urls = ([args.prefill_peer] if args.prefill_peer else []) \
            + list(args.prefill_url or [])
        if args.disaggregation_mode == "decode" and not prefill_urls:
            log.error("--disaggregation-mode decode requires at least one "
                      "--prefill-url (or --prefill-peer)")
            return 2

        if dist is not None and not dist.is_leader:
            # followers never serve HTTP: they join the mesh, then replay
            # the leader's op stream (SPMD requires identical programs in
            # identical order on every process)
            engine = load_engine(args, dist, ledger)
            sub = multihost.OpSubscriber(dist.coordinator_host,
                                         control_port)
            log.info("follower %d/%d replaying leader ops",
                     dist.process_id, dist.num_processes)
            try:
                return multihost.follower_loop(
                    engine, sub,
                    pd_export=(args.disaggregation_mode == "prefill"))
            finally:
                sub.close()

        embedder = None
        pd_prefill = None
        journal = None
        reqlog = None
        span_log = None
        if args.span_log:
            from ..telemetry.tracing import SpanLog
            span_log = SpanLog(args.span_log, component="engine")
            log.info("span timeline at %s", args.span_log)
        if args.journal and (args.task == "embed"
                             or args.disaggregation_mode == "prefill"):
            log.warning("--journal only applies to generation/decode "
                        "scheduling; ignoring it for this role")
        if args.task == "embed":
            embedder = load_embedder(args)
            scheduler = _NullScheduler()
        elif args.disaggregation_mode == "prefill":
            from .pd import make_pd_prefill_handler
            engine = load_engine(args, dist, ledger)
            if dist is not None:
                # multi-host prefill pool: every /pd/prefill compute runs
                # SPMD across the group via the same op replication the
                # generation leader uses
                pub = multihost.OpPublisher(dist.num_processes - 1,
                                            port=control_port)
                engine = multihost.ReplicatedEngine(engine, pub)
            pd_prefill = make_pd_prefill_handler(engine)
            scheduler = _PrefillNodeScheduler(engine)
        else:
            engine = load_engine(args, dist, ledger)
            if args.disaggregation_mode == "decode":
                from ..telemetry.reqlog import coerce
                from .pd import RemotePrefillEngine
                # one shared JSONL reqlog: the server's request records
                # and the PD client's peer-failure records interleave in
                # the same file, joinable by trace id
                reqlog = coerce(args.request_log)
                engine = RemotePrefillEngine(
                    engine, peer_urls=prefill_urls,
                    timeout=args.pd_attempt_timeout,
                    local_fallback=args.pd_local_fallback,
                    request_log=reqlog,
                    span_log=span_log)
                log.info("PD decode node: prefill pool %s%s",
                         prefill_urls,
                         " (local fallback)" if args.pd_local_fallback
                         else "")
            if dist is not None:
                pub = multihost.OpPublisher(dist.num_processes - 1,
                                            port=control_port)
                engine = multihost.ReplicatedEngine(engine, pub)
            if (dist is None and args.disaggregation_mode == "none"
                    and args.prefix_cache_mb > 0):
                # cross-replica prefix reuse: a replica with a live prefix
                # cache is also a prefix DONOR — peers the router's fleet
                # directory points at this replica fetch hot prefix KV
                # over the same hardened /pd/prefill path PD uses
                # (docs/kv-hierarchy.md). int8-pool engines ship blobs
                # quantized at half the bytes.
                from .pd import make_pd_prefill_handler
                pd_prefill = make_pd_prefill_handler(engine)
            # prefill/decode overlap is single-host only: multi-host
            # leaders publish ops from ONE thread in execution order
            # (followers replay strictly sequentially); on PD decode nodes
            # it moves the remote KV fetch off the decode thread
            err = check_plan_preconditions(engine, args)
            if err is not None:
                log.error("%s", err)
                return 2
            if args.journal:
                from .journal import RequestJournal
                provenance = None
                if args.disaggregation_mode == "decode":
                    # admit records carry the PD topology, so a resumed
                    # process (and the chaos harness) can tell these
                    # requests re-prefill over the pool on replay
                    provenance = {"mode": "pd-decode",
                                  "peers": prefill_urls}
                journal = RequestJournal(
                    args.journal, fsync=args.journal_fsync,
                    compact_bytes=args.journal_compact_mb << 20,
                    provenance=provenance)
                log.info("request journal at %s (fsync=%s)",
                         journal.path, args.journal_fsync)
            from ..telemetry.flight import FlightRecorder
            flight = FlightRecorder(capacity=max(args.flight_events, 16))
            scheduler = Scheduler(engine, overlap=dist is None,
                                  max_restarts=args.max_restarts,
                                  max_queue_wait=args.max_queue_wait,
                                  pipeline_depth=args.pipeline_depth,
                                  spec_tokens=args.spec_tokens,
                                  steps_per_dispatch=args.steps_per_dispatch,
                                  journal=journal,
                                  span_log=span_log,
                                  flight=flight,
                                  flight_dump_dir=args.flight_dump_dir,
                                  class_weights=class_weights,
                                  class_wait_caps=class_wait_caps,
                                  priority_scheduling=not
                                  args.no_priority_scheduling)
    with _phase("tokenizer"):
        log.info("device memory after load, GB per device: %s",
                 device.memory_gb())
        tok = load_tokenizer(args.model_dir)
    with _phase("listen"):
        name = args.model_name or args.model_dir.rstrip("/").rsplit("/", 1)[-1]
        # measured weight-fetch throughput from the published fetch
        # manifest, advertised on /ready for the router's cold-start
        # Retry-After math (docs/model-fleet.md); None when the tree was
        # staged by something other than the weight plane
        from ..modelagent import weightplane
        fetch_bps = weightplane.published_fetch_bps(args.model_dir)
        server = EngineServer(scheduler, tokenizer=tok, model_name=name,
                              fetch_bps=fetch_bps, device=dev,
                              host=args.host, port=args.port,
                              embedder=embedder, pd_prefill=pd_prefill,
                              startup=_startup,
                              request_log=(reqlog if reqlog is not None
                                           else args.request_log),
                              profile_dir=args.profile_dir,
                              debug_endpoints=args.debug_endpoints,
                              # structured outputs work in every generation
                              # mode: masks ship inside the replicated op
                              # stream (multi-host) and the first token's
                              # mask rides the /pd/prefill request (PD)
                              structured=embedder is None)
        log.info("serving %s on %s:%d (%s)", name, args.host, server.port,
                 "embeddings" if embedder else
                 f"slots={scheduler.engine.max_slots}")
        # restart resume BEFORE serving: unfinished requests from the
        # previous process re-enter the queue ahead of new traffic
        if journal is not None:
            resume = getattr(scheduler, "resume_from_journal", None)
            if resume is not None:
                resume()
        server.start()
    # ready: compile seconds count as `serving` from here, and the one
    # list of phases goes to /metrics, /health and the span log
    ledger.mark_serving()
    took = _startup.ready()
    if not ledger.bound:
        ledger.bind(server.registry)  # a role with no scheduler
    _startup.publish(
        server.registry.gauge(
            "ome_engine_startup_phase_seconds",
            "Seconds of each start-up phase; the phases tile process "
            "creation to ready", labelnames=("phase",)),
        server.registry.gauge(
            "ome_engine_startup_seconds",
            "Seconds from process creation to the listener up"))
    _startup.write_spans(span_log, getattr(scheduler, "_span_ctx", None))
    log.info("ready %.2fs after process creation: %s", took, ", ".join(
        f"{name} {secs:.2f}" for name, secs in _startup.seconds().items()))
    ctl = DrainController(server, scheduler, grace=args.drain_grace,
                          journal=journal)
    try:
        ctl.install()
        # first signal starts the graceful drain; a second forces it
        ctl.wait()
    finally:
        server.stop()
        scheduler.stop()
        if span_log is not None:
            span_log.close()  # idempotent (Scheduler.stop also closes)
        if journal is not None:
            # stop() evicted leftovers with finish_reason=shutdown,
            # which flushed their final progress WITHOUT tombstones —
            # the replacement process resumes them
            journal.close()
        if dist is not None:
            # orderly group teardown: the stop op releases followers
            # from recv() so every process reaches jax.distributed
            # shutdown (which waits for ALL clients) instead of
            # deadlocking the leader's exit on a blocked worker
            engine._pub.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
