#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serve path still starts
on the chip.

Default run (one chip): serves Qwen/Qwen3-4B at its full published
size (no width, no depth cut; seeded random weights) through the
normal path — `python -m ome_tpu.engine.serve` behind
`python -m ome_tpu.router` — answers a handful of requests, shows
that the Pallas kernels are in the programs the server compiled,
checks logits against the XLA reference path, then serves once more
with int4 weights and an int8 KV pool. `--chips 4` runs only the
tensor-parallel path and what it is compared with.

The parent never imports jax (a process that has touched JAX holds
the chip, and a child then fails or hangs): it starts one child at a
time and reaps it before the next. The device named in the last line
is the one the serving child reported, never the parent's guess.

Every line on stdout is one JSON object; the last one is exactly
`{"ok": ..., "device": {"platform", "kind", "count"}}`. Any phase that
fails ends the run with `"ok": false` and exit code 1.

`--rehearse-cpu` is a switch of this script, not of the engine: a tiny
config on the CPU backend, every phase run for its control flow, and a
verdict that still demands a TPU — so it ends `"ok": false`, which is
the proof that the script cannot pass without a chip.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, "chiprun_out", "chip_smoke")
# the rule of ome_tpu/device.enable_compile_cache, restated here only
# to COUNT entries: the children set the directory themselves
CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
    os.path.join(HERE, ".jax_cache")

# Qwen/Qwen3-4B config.json (huggingface.co/Qwen/Qwen3-4B), the keys
# that shape the model; catalogued at config/models/qwen/qwen3-4b.yaml
# as 4.02B parameters, 40960 positions.
QWEN3_4B = {
    "architectures": ["Qwen3ForCausalLM"], "model_type": "qwen3",
    "hidden_size": 2560, "num_hidden_layers": 36,
    "num_attention_heads": 32, "num_key_value_heads": 8,
    "head_dim": 128, "intermediate_size": 9728, "vocab_size": 151936,
    "tie_word_embeddings": True, "rope_theta": 1000000,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 40960,
    "hidden_act": "silu", "attention_bias": False,
}
QWEN3_4B_PARAMS = 4.02e9      # fail unless param_count is within 0.5 %

# the rehearsal's stand-in: same family and block shape, toy widths
TINY = dict(QWEN3_4B, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=8, num_key_value_heads=4, head_dim=16,
            intermediate_size=256, vocab_size=8192,
            max_position_embeddings=512)

# v5e HBM as the chip's compiler counts it ("15.75G hbm" in its
# out-of-memory message): what weights + pool + workspace must fit
V5E_HBM_BYTES = int(15.75 * 2 ** 30)

# Logit tolerance, in units of the logits' own standard deviation
# (about 1.0 with these weights), kernel path against XLA reference
# path — or tp=4 against tp=1 — on the same bf16 weights. Both sides
# compute in bf16 with f32 accumulation and differ in summation order
# and in where the softmax weights are rounded, so every layer's
# attention output carries one bf16 rounding (2^-8 = 0.4 %) of
# difference into the residual stream. Over 36 layers that
# random-walks to 2^-8 x sqrt(36) = 2.3 % of the logit spread: the
# first chip run measured a mean |diff| of 1.9-2.1 % and, over 3 x 10^5
# logits, a largest of 13 % (five sigma). The bounds leave a factor of
# two: a path computing in less precision than the config states
# (fp8, 2^-4) would show a mean near 40 % and fail both.
LOGIT_MEAN_TOL = 0.04
LOGIT_MAX_TOL = 0.25

EOS_STOPS_ALLOWED = 1   # random weights may draw EOS (1 in 151936/token)


class Fail(Exception):
    """A phase failed; the run ends ok=false."""


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# -- small helpers (stdlib only) ------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cache_entries() -> int:
    try:
        return sum(1 for n in os.listdir(CACHE_DIR)
                   if n.endswith("-cache"))
    except FileNotFoundError:
        return 0


def http(url: str, body=None, timeout: float = 900.0):
    """-> (status, parsed JSON or text)."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            raw, code = r.read(), r.status
    except urllib.error.HTTPError as e:
        raw, code = e.read(), e.code
    try:
        return code, json.loads(raw)
    except ValueError:
        return code, raw.decode("utf-8", "replace")


def metric(text: str, name: str) -> float:
    m = re.search(rf"^{name}(?:{{[^}}]*}})? ([0-9.eE+-]+)$", text, re.M)
    return float(m.group(1)) if m else float("nan")


class Child:
    """One child process with its log file; always reaped."""

    def __init__(self, name: str, argv, env):
        self.name = name
        self.log_path = os.path.join(WORK, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            argv, cwd=HERE, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def tail(self, n: int = 30) -> str:
        return "\n".join(self.log_text().splitlines()[-n:])

    def stop(self, grace: float = 20.0) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                os.killpg(self.proc.pid, signal.SIGKILL)
                self.proc.wait(10)
        self._log.close()


def child_env(rehearse: bool, chips: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["PYTHONUNBUFFERED"] = "1"
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={chips}")
    return env


def make_prompt(rng: random.Random, n_bytes: int) -> str:
    """Seeded ASCII text; the byte tokenizer gives one token a byte.
    Words are random, so no two prompts share a 32-token prefix block
    and the prefix cache compiles no suffix program mid-run."""
    words = []
    size = 0
    while size < n_bytes:
        w = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz")
                    for _ in range(rng.randint(2, 9)))
        words.append(w)
        size += len(w) + 1
    return " ".join(words)[:n_bytes]


# -- sizing ----------------------------------------------------------


def size_pool(cfg: dict, slots: int, max_seq: int, block: int) -> dict:
    """KV pool blocks for the bf16 phase, by the arithmetic of
    ome_tpu/perf/hbm.py (row = layers x kv_heads x (Dk + Dv) x 2 B).

    What has to fit in HBM at once: the weights; the pool ONCE (the
    paged decode program carries the donated pool through its layer
    scan and writes and reads it in place: the chip compiler's
    memory_analysis shows 11 MB of temporaries beside it, not a
    second pool); one 2048-bucket prefill's KV, in flight on the
    admission thread while decode runs, twice (its output and the
    insert's argument); the default 256 MiB prefix cache; and 0.5 GiB
    of margin for sampling buffers and allocator fragmentation. At
    Qwen3-4B's size that leaves room for more blocks than 16 slots of
    2048 can fill, so the dense equivalent caps it."""
    hidden, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, mlp, vocab = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    per_layer = (hidden * heads * dh + 2 * hidden * kv_heads * dh
                 + heads * dh * hidden + 3 * hidden * mlp
                 + 2 * hidden + 2 * dh)
    n_params = vocab * hidden + layers * per_layer + hidden
    weights = 2 * n_params
    row = layers * kv_heads * 2 * dh * 2
    prefill_kv = 2 * max_seq * row
    budget = (V5E_HBM_BYTES - weights - prefill_kv - (256 << 20)
              - (512 << 20))
    blocks = budget // (block * row)
    dense_equivalent = slots * -(-max_seq // block)
    blocks = int(max(min(blocks, dense_equivalent), 2))
    return {"kv_blocks": blocks + 1,      # +1: block 0 is the trash block
            "expected_params": n_params,
            "weights_gb": round(weights / 1e9, 2),
            "pool_gb": round(blocks * block * row / 1e9, 2),
            "pool_tokens": blocks * block,
            "dense_equivalent_blocks": dense_equivalent,
            "hbm_gb": round(V5E_HBM_BYTES / 1e9, 2)}


# -- phases driven by the parent ------------------------------------


def wait_healthy(child: Child, url: str, want_platform, timeout: float):
    """Poll /health; fail fast when the child dies or names a device
    of the wrong platform in its start-up line."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if child.proc.poll() is not None:
            raise Fail(f"{child.name} exited rc={child.proc.returncode} "
                       f"before serving:\n{child.tail()}")
        m = re.search(r"device: platform=(\S+)", child.log_text())
        if m and want_platform and m.group(1) != want_platform:
            raise Fail(f"{child.name} runs on platform {m.group(1)!r}, "
                       f"not {want_platform!r}")
        try:
            code, body = http(url + "/health", timeout=2.0)
            if code == 200:
                return body
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.5)
    raise Fail(f"{child.name} not healthy after {timeout:.0f}s:\n"
               f"{child.tail()}")


def complete(url: str, prompt: str, max_tokens: int, stream=False,
             **sampling) -> dict:
    """One /v1/completions request -> {tokens, finish_reason}."""
    body = dict(prompt=prompt, max_tokens=max_tokens, stream=stream,
                **sampling)
    if not stream:
        code, out = http(url + "/v1/completions", body)
        if code != 200:
            raise Fail(f"request answered {code}: {str(out)[:300]}")
        return {"tokens": out["usage"]["completion_tokens"],
                "finish_reason": out["choices"][0]["finish_reason"]}
    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    events = []
    with urllib.request.urlopen(req, timeout=900.0) as r:
        if r.status != 200:
            raise Fail(f"stream answered {r.status}")
        if "text/event-stream" not in r.headers.get("Content-Type", ""):
            raise Fail("stream did not answer text/event-stream")
        for line in r:
            line = line.decode().strip()
            if line.startswith("data: "):
                events.append(line[6:])
    if not events or events[-1] != "[DONE]":
        raise Fail(f"stream did not end with [DONE]: {events[-2:]}")
    last = json.loads(events[-2])
    return {"tokens": last["usage"]["completion_tokens"],
            "finish_reason": last["choices"][0]["finish_reason"],
            "events": len(events)}


def check_answers(answers, wanted) -> int:
    """Every request returned 200 (complete() raised otherwise) with
    the number of tokens it asked for; an EOS drawn from the random
    weights is the one admitted exception."""
    stops = 0
    for got, want in zip(answers, wanted):
        if got["finish_reason"] == "stop" and got["tokens"] <= want:
            stops += 1
        elif got["tokens"] != want or got["finish_reason"] != "length":
            raise Fail(f"asked {want} tokens, got {got}")
    if stops > EOS_STOPS_ALLOWED:
        raise Fail(f"{stops} requests stopped early on EOS")
    return sum(a["tokens"] for a in answers)


def serve_phase(name: str, sizes: dict, serve_args, env, rng,
                want_platform, through_router: bool, rehearse: bool):
    """Start the server (and the router in front of it), warm every
    program shape, then serve the request mix; returns the /health
    body and leaves nothing running."""
    port = free_port()
    argv = [sys.executable, "-m", "ome_tpu.engine.serve",
            "--model-dir", os.path.join(WORK, "model"),
            "--model-name", "qwen3-4b-random", "--random-weights",
            "--host", "127.0.0.1", "--port", str(port),
            "--debug-endpoints", "--ledger-mode", "full"] + serve_args
    say(name, event="start", argv=" ".join(argv[1:]))
    cache_before = cache_entries()
    t0 = time.time()
    server = Child(f"{name}-server", argv, env)
    router = None
    try:
        engine_url = f"http://127.0.0.1:{port}"
        health = wait_healthy(server, engine_url, want_platform, 600.0)
        url = engine_url
        if through_router:
            rport = free_port()
            router = Child(f"{name}-router", [
                sys.executable, "-m", "ome_tpu.router", "--backend",
                engine_url, "--port", str(rport), "--bind",
                "127.0.0.1"], env)
            url = f"http://127.0.0.1:{rport}"
            wait_healthy(router, url, None, 60.0)
        setup_s = time.time() - t0
        m = re.search(r"initialized random weights: ([0-9.]+)M params",
                      server.log_text())
        n_params = float(m.group(1)) * 1e6 if m else 0.0
        if abs(n_params / sizes["expected_params"] - 1) > 0.005:
            raise Fail(f"server initialised {n_params/1e9:.3f}B "
                       f"parameters, config says "
                       f"{sizes['expected_params']/1e9:.3f}B")
        short, long_ = sizes["short_bytes"], sizes["long_bytes"]
        # warm-up: one request per program shape (short bucket, long
        # bucket, decode) — the server compiles at first use
        t1 = time.time()
        warm = [complete(url, make_prompt(rng, short), 4, temperature=0),
                complete(url, make_prompt(rng, long_), 4, temperature=0)]
        check_answers(warm, [4, 4])
        compile_s = time.time() - t1
        _, progs = http(engine_url + "/debug/programs")
        n_programs = progs["count"]
        _, met0 = http(engine_url + "/metrics")
        # the served window: greedy and sampled, an SSE stream, the
        # long prompt, then six at once so slots batch continuously
        t2 = time.time()
        n = sizes["gen_tokens"]
        answers = [
            complete(url, make_prompt(rng, short), n, temperature=0),
            complete(url, make_prompt(rng, short), n, temperature=0.8,
                     top_p=0.95, top_k=40),
            complete(url, make_prompt(rng, short), n, stream=True,
                     temperature=0),
            complete(url, make_prompt(rng, long_), n, temperature=0),
        ]
        wanted = [n, n, n, n]
        mix = [(short, n, 0.0), (short, n + 8, 0.7), (long_, n, 0.0),
               (short, n + 16, 0.0), (short, n, 1.0), (short, n + 4, 0.0)]
        prompts = [make_prompt(rng, b) for b, _, _ in mix]
        results = [None] * len(mix)

        def one(i):
            results[i] = complete(url, prompts[i], mix[i][1],
                                  temperature=mix[i][2])

        if sizes.get("concurrent", True):
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(len(mix))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(900.0)
            if any(r is None for r in results):
                raise Fail("a concurrent request did not complete "
                           f"(see {server.log_path})")
            answers += results
            wanted += [m_[1] for m_ in mix]
        tokens = check_answers(answers, wanted)
        serve_s = time.time() - t2
        _, met1 = http(engine_url + "/metrics")
        _, progs = http(engine_url + "/debug/programs")
        code, health = http(engine_url + "/health")
        if code != 200 or health["status"] != "ok":
            raise Fail(f"/health after serving: {code} {health}")
        steps = (metric(met1, "ome_engine_decode_steps_total")
                 - metric(met0, "ome_engine_decode_steps_total"))
        made = (metric(met1, "ome_engine_tokens_generated_total")
                - metric(met0, "ome_engine_tokens_generated_total"))
        say(name, event="served", requests=len(answers), tokens=tokens,
            setup_s=round(setup_s, 1), compile_s=round(compile_s, 1),
            serve_s=round(serve_s, 1),
            programs_compiled_in_window=progs["count"] - n_programs,
            tokens_per_decode_step=round(made / max(steps, 1), 2),
            cache_entries_before=cache_before,
            cache_entries_after=cache_entries(),
            hbm_peak_gb=round(
                metric(met1, "ome_engine_hbm_peak_bytes") / 1e9, 2),
            hbm_limit_gb=round(
                metric(met1, "ome_engine_hbm_bytes_limit") / 1e9, 2))
        if sizes.get("concurrent", True) and not made / max(steps, 1) > 1:
            raise Fail("concurrent requests never shared a decode step")
        evidence(name, progs["programs"], health, server.log_text(),
                 sizes, rehearse)
        return health
    finally:
        if router is not None:
            router.stop()
        server.stop(40.0)


def evidence(name, programs, health, log_text, sizes, rehearse):
    """Phase 3: that the kernels ran, from what the server itself
    compiled and reports — not from a gate's opinion."""
    by_name = {p["program"]: p for p in programs}
    say(name, event="programs", programs=[
        {k: p[k] for k in ("program", "source", "mosaic_calls",
                           "kernel_declines", "temp_bytes",
                           "dispatches")} for p in programs])
    say(name, event="health", device=health["device"],
        engine=health["engine"], degradations=health["degradations"])
    if any(health["degradations"].values()):
        raise Fail(f"planner degradations: {health['degradations']}")
    if sizes["paged"] and ("FALLING BACK to the dense" in log_text
                           or not health["engine"]["paged_kv"]):
        raise Fail("the paged pool is not live")
    for prog, min_calls, declines_ok in sizes["need"]:
        if prog not in by_name:
            raise Fail(f"server never compiled {prog}: "
                       f"{sorted(by_name)}")
        calls = by_name[prog]["mosaic_calls"]
        declines = by_name[prog]["kernel_declines"]
        if not isinstance(calls, int) or declines is None:
            raise Fail(f"{prog}: the compiled program was not read")
        if rehearse:
            continue    # no Mosaic on the CPU backend
        if calls < min_calls or (declines and not declines_ok):
            raise Fail(f"{prog} holds {calls} Mosaic custom calls "
                       f"(needs {min_calls}), declines: {declines}")


def run_child(name: str, spec: dict, env) -> dict:
    """Run `chip_smoke.py --child <json>`; its last stdout line is its
    result."""
    cache_before = cache_entries()
    child = Child(name, [sys.executable, os.path.abspath(__file__),
                         "--child", json.dumps(spec)], env)
    try:
        try:
            rc = child.proc.wait(900.0)
        except subprocess.TimeoutExpired:
            raise Fail(f"{name} did not finish in 900 s:\n{child.tail()}")
        if rc != 0:
            raise Fail(f"{name} exited rc={rc}:\n{child.tail()}")
        lines = [ln for ln in child.log_text().splitlines()
                 if ln.startswith("{")]
        result = json.loads(lines[-1])
    finally:
        child.stop()
    result["cache_entries_before"] = cache_before
    result["cache_entries_after"] = cache_entries()
    say(name, event="result", **result)
    if not result.get("ok"):
        raise Fail(f"{name}: {result.get('error', 'comparison failed')}")
    return result


# -- children that touch JAX ----------------------------------------


def _child_main(spec: dict) -> int:
    """Runs in a child: build engines with serve.load_engine from the
    server's own arguments and compare logits two ways (kernel path
    against XLA reference, or tp=4 against tp=1)."""
    import dataclasses
    import functools

    import numpy as np

    from ome_tpu import device
    device.enable_compile_cache()
    import jax

    from ome_tpu.engine import serve
    from ome_tpu.engine.core import DecodeState
    from ome_tpu.engine.tokenizer import ByteTokenizer
    from ome_tpu.models import llama
    from ome_tpu.ops import attention as attn_ops
    from ome_tpu.ops import int4_matmul

    t0 = time.time()
    out = {"ok": False, "device": device.identity()}

    def build(argv):
        args = serve.build_parser().parse_args(
            ["--model-dir", os.path.join(WORK, "model"),
             "--random-weights", "--ledger-mode", "full"] + argv)
        return serve.load_engine(args)

    @contextlib.contextmanager
    def xla_reference():
        # the repo's own reference switches: attention(backend="xla")
        # and paged_attention_xla through OME_ATTN_BACKEND (read when
        # a program is traced), the XLA dequant matmul through
        # int4_matmul.kernel_disabled
        os.environ["OME_ATTN_BACKEND"] = "xla"
        try:
            with int4_matmul.kernel_disabled():
                yield
        finally:
            del os.environ["OME_ATTN_BACKEND"]

    def probes(engine, scope):
        """(prefill_logits(ids), decode_logits(state)) for `engine`,
        traced inside `scope()`: the engine's forward with the
        sampling cut off, so logits can be compared."""
        cfg = engine.cfg

        @functools.partial(jax.jit, static_argnames=("bucket",))
        def prefill(params, padded, true_len, bucket):
            cache = llama.KVCache.create(cfg, 1, bucket)
            logits, _ = llama.forward(params, cfg, padded, cache=cache,
                                      logits_at=true_len - 1)
            return logits[:, 0]

        @functools.partial(jax.jit, donate_argnums=(1,))
        def decode(params, state, table):
            toks = state.tokens[:, None]
            if engine.kv_block:
                cache = llama.PagedKVCache(
                    k=state.k, v=state.v, index=state.lengths,
                    table=table, k_scale=state.k_scale,
                    v_scale=state.v_scale)
                logits, nc = llama.forward_paged(
                    params, cfg, toks, cache,
                    adapter_ids=state.adapters)
                scales = (nc.k_scale, nc.v_scale)
            else:
                cache = llama.KVCache(k=state.k, v=state.v,
                                      index=state.lengths)
                logits, nc = llama.forward(
                    params, cfg, toks, cache=cache,
                    adapter_ids=state.adapters)
                scales = (None, None)
            # lengths stay put: the row just written at `lengths` is
            # rewritten identically by the next probe of this state
            return logits[:, 0], DecodeState(
                k=nc.k, v=nc.v, lengths=state.lengths,
                tokens=state.tokens, adapters=state.adapters,
                k_scale=scales[0], v_scale=scales[1])

        def prefill_logits(ids):
            bucket = next(b for b in engine.prefill_buckets
                          if len(ids) <= b)
            padded = np.asarray([ids + [0] * (bucket - len(ids))],
                                np.int32)
            with scope():
                return np.asarray(prefill(
                    engine.params, padded,
                    np.asarray([len(ids)], np.int32), bucket=bucket))

        def decode_logits(state):
            table = engine._table_dev if engine.kv_block else None
            with scope():
                logits, state = decode(engine.params, state, table)
            return np.asarray(logits), state

        return prefill_logits, decode_logits

    def drive(engine, prompts, forced):
        """The engine's own programs: prefill + insert each prompt,
        one decode step on forced input tokens (so two engines stay
        on the same sequence whatever their argmax does), then leave
        the state ready for a decode probe."""
        state = engine.new_state()
        first = []
        for slot, ids in enumerate(prompts):
            tok, kv, true_len, bucket = engine.prefill(ids)
            state = engine.insert(state, kv, slot, true_len, tok,
                                  bucket)
            first.append(tok)
        B = engine.max_slots
        greedy = (np.zeros(B, np.float32), np.zeros(B, np.int32),
                  np.ones(B, np.float32))

        def force(state, toks):
            arr = np.zeros(B, np.int32)
            arr[:len(toks)] = toks
            return dataclasses.replace(state, tokens=jax.device_put(
                arr, state.tokens.sharding))

        state = force(state, forced[0])
        state, toks = engine.decode(state, *greedy)
        jax.block_until_ready(toks)
        return force(state, forced[1]), first

    def compare(a, b, rows):
        a, b = a[rows].astype(np.float64), b[rows].astype(np.float64)
        if not (np.isfinite(a).all() and np.isfinite(b).all()):
            return {"finite": False}
        return {"finite": True, "shape": list(a.shape),
                "max_abs_diff": float(np.abs(a - b).max()),
                "mean_abs_diff": float(np.abs(a - b).mean()),
                "logit_std": float(a.std()),
                "argmax_agree": bool((a.argmax(-1)
                                      == b.argmax(-1)).all())}

    rng = random.Random(spec["seed"])
    encode = ByteTokenizer().encode
    prompts = [encode(make_prompt(rng, n)) for n in spec["prompt_bytes"]]
    vocab = spec["vocab"]
    forced = [[rng.randrange(3, vocab) for _ in prompts]
              for _ in range(2)]
    rows = list(range(len(prompts)))
    checks = {}

    if spec["kind"] == "reference":
        engine = build(spec["serve_args"])
        out["setup_s"] = round(time.time() - t0, 1)
        out["param_count"] = int(llama.param_count(engine.params))
        t1 = time.time()
        state, first = drive(engine, prompts, forced)
        p_kernel, d_kernel = probes(engine, contextlib.nullcontext)
        p_ref, d_ref = probes(engine, xla_reference)
        for i, ids in enumerate(prompts):
            checks[f"prefill_{len(ids)}"] = compare(
                p_kernel(ids), p_ref(ids), [0])
            checks[f"prefill_{len(ids)}"]["engine_token_is_argmax"] = \
                bool(first[i] == int(p_kernel(ids)[0].argmax()))
        got, state = d_kernel(state)
        want, state = d_ref(state)
        checks["decode"] = compare(got, want, rows)
    else:  # "tp": tp=4 against tp=1, both built by serve.load_engine
        wide = build(spec["serve_args"] + ["--tp", str(spec["tp"])])
        out["memory_gb_per_device_after_load"] = device.memory_gb()
        narrow = build(spec["serve_args"] + ["--max-slots",
                                             str(len(prompts))])
        out["setup_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        p_wide, d_wide = probes(
            wide, lambda: attn_ops.heads_sharded_over(wide.mesh))
        p_narrow, d_narrow = probes(narrow, contextlib.nullcontext)
        for ids in prompts:
            checks[f"prefill_{len(ids)}"] = compare(
                p_wide(ids), p_narrow(ids), [0])
        s_wide, _ = drive(wide, prompts, forced)
        s_narrow, _ = drive(narrow, prompts, forced)
        checks["decode"] = compare(d_wide(s_wide)[0],
                                   d_narrow(s_narrow)[0], rows)
        mem = out["memory_gb_per_device_after_load"]
        if len(mem) != spec["tp"] or (max(mem) > 0 and
                                      max(mem) > 1.15 * min(mem)):
            out["error"] = (f"weights are not in {spec['tp']} equal "
                            f"shares: {mem} GB per device")
    out["compile_and_compare_s"] = round(time.time() - t1, 1)
    out["peak_gb_per_device"] = device.memory_gb("peak_bytes_in_use")
    out["checks"] = checks
    out["tolerance"] = {"mean": LOGIT_MEAN_TOL, "max": LOGIT_MAX_TOL,
                        "unit": "logit_std"}
    bad = [name for name, c in checks.items() if not c["finite"]
           or c["mean_abs_diff"] > LOGIT_MEAN_TOL * c["logit_std"]
           or c["max_abs_diff"] > LOGIT_MAX_TOL * c["logit_std"]]
    if bad and "error" not in out:
        out["error"] = f"logits outside tolerance: {bad}"
    out["ok"] = "error" not in out
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


# -- the run ---------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the tp=4 path and its comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny config on the CPU backend; the verdict "
                         "still demands a TPU, so this ends ok=false")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return _child_main(json.loads(args.child))

    rehearse = args.rehearse_cpu
    cfg = TINY if rehearse else QWEN3_4B
    want_platform = "cpu" if rehearse else "tpu"
    env = child_env(rehearse, args.chips)
    rng = random.Random(args.seed)
    device = None
    ok = False
    try:
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(os.path.join(WORK, "model"))
        with open(os.path.join(WORK, "model", "config.json"), "w") as f:
            json.dump(cfg, f, indent=1)
        if rehearse:
            slots, max_seq, block = 4, 256, 16
            sizes = dict(short_bytes=40, long_bytes=200,
                         long_bucket=256, gen_tokens=16)
        else:
            slots, max_seq, block = 16, 2048, 128
            sizes = dict(short_bytes=48, long_bytes=1800,
                         long_bucket=2048, gen_tokens=32)
        sizes.update(size_pool(cfg, slots, max_seq, block))
        if not rehearse and abs(
                sizes["expected_params"] / QWEN3_4B_PARAMS - 1) > 0.005:
            raise Fail(f"config gives {sizes['expected_params']/1e9:.3f}B"
                       f" parameters, Qwen3-4B has 4.02B")
        say("sizing", chips=args.chips, slots=slots, max_seq=max_seq,
            kv_block=block, **sizes)
        common = ["--max-slots", str(slots), "--max-seq", str(max_seq)]
        long_prefill = f"prefill[bucket={sizes['long_bucket']}]"
        check_spec = dict(seed=args.seed, vocab=cfg["vocab_size"],
                          prompt_bytes=[
                              sizes["short_bytes"], sizes["long_bytes"]])
        if args.chips == 4:
            health = serve_phase(
                "tp4", dict(sizes, paged=False, need=[
                    ("decode", 1, False), (long_prefill, 1, False)]),
                common + ["--tp", "4"], env, rng, want_platform,
                through_router=False, rehearse=rehearse)
            device = health["device"]
            if health["engine"]["tp"] != 4:
                raise Fail(f"server reports tp={health['engine']['tp']}")
            run_child("tp4-vs-tp1", dict(check_spec, kind="tp", tp=4,
                                         serve_args=common), env)
        else:
            bf16 = common + ["--kv-block", str(block), "--kv-blocks",
                             str(sizes["kv_blocks"])]
            health = serve_phase(
                "serve-bf16", dict(sizes, paged=True, need=[
                    ("decode_paged", 1, False),
                    (long_prefill, 1, False)]),
                bf16, env, rng, want_platform, through_router=True,
                rehearse=rehearse)
            device = health["device"]
            ref = run_child("reference-bf16", dict(
                check_spec, kind="reference", serve_args=bf16), env)
            if abs(ref["param_count"] / sizes["expected_params"] - 1) \
                    > 0.005:
                raise Fail(f"engine holds {ref['param_count']/1e9:.3f}B "
                           f"parameters")
            quant = common + ["--kv-block", str(block), "--quantization",
                              "int4", "--kv-dtype", "int8"]
            # decode holds the int4 kernel at each of wq wk wv w_gate
            # w_up plus the int8 pool kernel; the long prefill's rows
            # exceed the int4 kernel's MAX_M, a decline by design,
            # which leaves flash attention
            health = serve_phase(
                "serve-int4-kvint8", dict(
                    sizes, paged=True, concurrent=False, need=[
                        ("decode_paged", 6, False),
                        (long_prefill, 1, True)]),
                quant, env, rng, want_platform, through_router=False,
                rehearse=rehearse)
            run_child("reference-int4-kvint8", dict(
                check_spec, kind="reference", serve_args=quant), env)
        want = {"platform": "tpu", "count": args.chips}
        got = {k: device.get(k) for k in want}
        if got != want:
            raise Fail(f"served on {device}, the smoke needs {want}")
        ok = True
    except Fail as e:
        say("failed", error=str(e))
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
