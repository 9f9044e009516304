#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent never imports JAX. It writes the model directory, starts
`python -m ome_tpu.engine.serve` as an operator would and
`python -m ome_tpu.router` in front of it, warms every shape the cell's
traffic uses (all of that is `setup_s`), offers the cell's traffic for
`--seconds` through the router as SSE streams, stops both, and then
lets one child (`check.py`) hold the chip to compare what the window
served with the plain reference. The last line of stdout is the one
JSON object of the contract; a run that cannot measure (no TPU, fewer
chips than the cell asks, no program to serve) prints no result and
exits non-zero.

Everything that belongs to one cell, configuration, traffic mix or
metric is data or a file of its own, found by the names in
`BENCHMARK.json`; this file holds no cell's and no model's name.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import random
import shutil
import sys
import threading
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import client  # noqa: E402
import modeldir  # noqa: E402
import procs  # noqa: E402
import traffic  # noqa: E402
from procs import Fail  # noqa: E402
from stats import lateness, percentile  # noqa: E402

CHECK_SEQUENCES = 32      # greedy requests compared after a window


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, bench_root: str = ROOT) -> Dict:
    """Resolve a cell's files by the names `BENCHMARK.json` gives:
    the configuration's `file`, `<paths[0]>/traffic/<traffic>.json`
    and, where it exists, `<paths[0]>/cells/<workload>.json`, all
    under `bench_root` (the checkout; a fixture tree in the tests)."""
    bench = load_json(bench_root, "BENCHMARK.json")
    data = os.path.join(bench_root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Fail(f"no workload {workload!r} in BENCHMARK.json: "
                   f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config_path = os.path.join(bench_root, configs[cell["config"]]["file"])
    config = load_json(config_path)
    spec = load_json(data, "traffic", cell["traffic"] + ".json")
    own = os.path.join(data, "cells", workload + ".json")
    if os.path.exists(own):
        with open(own) as f:
            spec.update(json.load(f).get("traffic", {}))

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "config": config, "config_path": config_path,
            "traffic": spec, "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def load_reader(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, which has one entry point
    `read(ctx) -> number or None`."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def child_env(extra: Optional[Dict[str, str]]) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra or {})
    return env


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")


def warm_up(url: str, cell: Dict, vocab: int, rng: random.Random) -> int:
    """One request for every prefill bucket the cell's prompts can
    reach, greedy and sampled, then a few at once; asserts that every
    generated token came as a chunk of its own."""
    lo, hi = traffic.prefill_lengths(cell["traffic"])
    buckets = cell["config"]["benchmark"]["prefill_buckets"]
    lengths, prev = [], 0
    for b in buckets:
        if b >= lo and prev < hi:
            lengths.append(min(b, hi))
        prev = b
    temp = float(cell["traffic"].get("temperature", 0.0))
    planned = [traffic.Planned(
        index=i, due_s=None, client=0, prompt_tokens=n, max_tokens=6,
        temperature=temp if i % 2 else 0.0,
        prompt_seed=rng.getrandbits(48)) for i, n in enumerate(lengths)]
    burst = [traffic.Planned(
        index=len(planned) + i, due_s=None, client=1 + i,
        prompt_tokens=lengths[0], max_tokens=6, temperature=temp,
        prompt_seed=rng.getrandbits(48)) for i in range(4)]
    planned += burst
    # client 0 sends the buckets one after another; the burst's four
    # clients start at once with it, which also batches decode
    got = client.drive(url, planned, vocab, seconds=600.0, drain_s=600.0,
                       exhaust_ok=True)
    for a in got["answers"]:
        if a.failed:
            raise Fail(f"warm-up request {a.index} failed: status "
                       f"{a.status} {a.error}")
        if len(a.arrivals) != a.usage_tokens or a.token_ids() is None:
            raise Fail(
                f"warm-up request {a.index}: {len(a.arrivals)} chunks for "
                f"{a.usage_tokens} generated tokens ({a.words[:3]}): the "
                "server is not streaming one chunk a token (did the "
                "synthetic tokenizer load?)")
    return len(got["answers"])


def pick_samples(answers: List[client.Answer], rng: random.Random) -> List:
    """Greedy requests the window finished, drawn from the seed, the
    longest always among them."""
    done = [a for a in answers
            if not a.failed and a.temperature == 0.0 and a.token_ids()]
    if not done:
        return []
    longest = max(done, key=lambda a: len(a.prompt_ids) + len(a.words))
    rest = [a for a in done if a is not longest]
    rng.shuffle(rest)
    return [longest] + rest[:CHECK_SEQUENCES - 1]


class Served:
    """The system under test, up and warm: server and router children
    started as an operator starts them, every shape of the cell's
    traffic compiled. `window()` offers one window of traffic;
    `stop()` reaps both children. `require_tpu`, `serve_module`,
    `env_extra`, `serve_extra` and `bench_root` exist for the tests
    under tests/benchmark (a rehearsal on the CPU; the timed path
    broken underneath) and for calibrate.py (the program's own
    lower-precision paths); the benchmark's command line reaches none
    of them."""

    def __init__(self, workload: str, seed: int, trace: bool, *,
                 require_tpu: bool = True,
                 serve_module: str = "ome_tpu.engine.serve",
                 env_extra: Optional[Dict[str, str]] = None,
                 serve_extra: Optional[List[str]] = None,
                 bench_root: str = ROOT):
        if not os.path.isdir(os.path.join(ROOT, "ome_tpu")):
            raise Fail(f"no program to serve: {ROOT}/ome_tpu is missing")
        self.workload, self.trace = workload, trace
        self.require_tpu, self.env_extra = require_tpu, env_extra
        self.cell = load_cell(workload, bench_root)
        self.config = self.cell["config"]
        self.bench_cfg = self.config["benchmark"]
        self.vocab = self.config["vocab_size"]
        self.env = child_env(env_extra)
        self.work = os.path.join(ROOT, ".bench_work", workload)
        self.logs = os.path.join(ROOT, "chiprun_out", "bench", workload)
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        os.makedirs(self.logs, exist_ok=True)
        self.children: List[procs.Child] = []
        chips = int(self.cell["cell"]["chips"])
        t_setup = time.monotonic()
        try:
            model_dir = os.path.join(self.work, "model")
            modeldir.write(model_dir, self.config)
            self.reqlog = os.path.join(self.work, "requests.jsonl")
            self.profile_dir = os.path.join(self.work, "profile")
            port, rport = procs.free_port(), procs.free_port()
            argv = [sys.executable, "-m", serve_module,
                    "--model-dir", model_dir, "--model-name",
                    self.cell["cell"]["config"], "--random-weights",
                    "--host", "127.0.0.1", "--port", str(port),
                    "--request-log", self.reqlog, "--debug-endpoints"]
            argv += [str(a) for a in self.bench_cfg["serve_args"]]
            argv += serve_extra or []
            if trace:
                argv += ["--profile-dir", self.profile_dir]
            cache_cold = procs.cache_entries(cache_dir())
            self.server = procs.Child("server", argv, self.env, ROOT,
                                      self.logs)
            self.children.append(self.server)
            self.engine_url = f"http://127.0.0.1:{port}"
            health = procs.wait_healthy(self.server, self.engine_url, 1100.0)
            self.device = health.get("device") or {}
            if require_tpu and (self.device.get("platform") != "tpu"
                                or self.device.get("count") != chips):
                raise Fail(f"the cell needs {chips} TPU chip(s); the "
                           f"server runs on {self.device}")
            self.peaks = load_json(HERE, "peaks.json").get(
                self.device.get("kind"))
            if require_tpu and self.peaks is None:
                raise Fail(f"device kind {self.device.get('kind')!r} is "
                           "not in benchmark/peaks.json")
            router = procs.Child("router", [
                sys.executable, "-m", "ome_tpu.router", "--backend",
                self.engine_url, "--port", str(rport), "--bind",
                "127.0.0.1"], self.env, ROOT, self.logs)
            self.children.append(router)
            self.url = f"http://127.0.0.1:{rport}"
            procs.wait_healthy(router, self.url, 120.0)
            n_warm = warm_up(self.url, self.cell, self.vocab,
                             random.Random(seed))
            self.setup_s = time.monotonic() - t_setup
            say(phase="setup", setup_s=round(self.setup_s, 3),
                warm_requests=n_warm, programs=self.programs()["count"],
                cache_entries_before_setup=cache_cold,
                cache_entries_after_setup=procs.cache_entries(cache_dir()),
                device=self.device)
        except BaseException:
            self.stop()
            raise

    def programs(self) -> Dict:
        code, body = procs.http(self.engine_url + "/debug/programs")
        if code != 200:
            raise Fail(f"/debug/programs answered {code}")
        return body

    def window(self, seed: int, seconds: float,
               overrides: Optional[Dict] = None) -> Dict:
        """Offer one window of the cell's traffic; returns the context
        the metric readers take."""
        spec = dict(self.cell["traffic"], **(overrides or {}))
        planned = traffic.plan(spec, seed, seconds)
        progs_before = self.programs()
        cache_before = procs.cache_entries(cache_dir())
        metrics_before = procs.scrape(self.engine_url)
        samples: List[Dict[str, float]] = []
        profile: Dict = {}
        stop = threading.Event()

        def sampler():
            while not stop.wait(0.5):
                try:
                    samples.append(procs.scrape(self.engine_url))
                except Exception:
                    pass

        def profiler(t0: float):
            trace_s = max(min(3.0, seconds / 4.0), 0.2)
            time.sleep(max(t0 + 0.4 * seconds - time.monotonic(), 0))
            code, body = procs.http(
                self.engine_url + f"/debug/profile?seconds={trace_s}",
                body={}, timeout=120.0)
            profile.update(body if isinstance(body, dict) else {},
                           status=code, asked_s=trace_s)

        threads = []

        def on_start(t0: float):
            if self.trace:
                threads.append(threading.Thread(target=sampler, daemon=True))
                threads.append(threading.Thread(target=profiler, args=(t0,),
                                                daemon=True))
                for t in threads:
                    t.start()

        got = client.drive(self.url, planned, self.vocab, seconds,
                           drain_s=90.0, on_start=on_start)
        stop.set()
        for t in threads:
            t.join(150.0)
        answers = got["answers"]
        failed = [a for a in answers if a.failed]
        early = [a for a in answers if not a.failed
                 and a.usage_tokens is not None
                 and a.usage_tokens < a.max_tokens]
        say(phase="window", seed=seed, attempted=len(answers),
            failed=len(failed),
            failed_status=sorted({a.status for a in failed}),
            ended_early=len(early), generator_lateness=lateness(
                [a.due for a in answers], [a.sent for a in answers]))
        ttft = [1e3 * (a.arrivals[0] - a.due) for a in answers
                if not a.failed]
        say(phase="latency", note="for reading; the metrics are the "
            "readers' own", ttft_ms={
                "mean": sum(ttft) / max(len(ttft), 1),
                **{f"p{p}": percentile(ttft, p)
                   for p in (50, 75, 90, 95, 100)}})
        _, health = procs.http(self.engine_url + "/health")
        metrics_after = procs.scrape(self.engine_url)
        progs_after = self.programs()
        known = {p["program"] for p in progs_before["programs"]}

        def moved(name):
            return metrics_after.get(name, 0.0) - metrics_before.get(name, 0.0)

        say(phase="engine", preemptions=moved("ome_engine_preemptions_total"),
            prefix_hits=moved("ome_engine_prefix_cache_hits_total"),
            new_programs=[p["program"] for p in progs_after["programs"]
                          if p["program"] not in known])
        return {"answers": answers, "failed": failed, "t0": got["t0"],
                "t1": got["t1"], "seconds": seconds,
                "setup_s": self.setup_s, "metrics_before": metrics_before,
                "metrics_after": metrics_after,
                "gauge_samples": samples, "profile": profile,
                "config": self.config, "peaks": self.peaks,
                "programs_before": progs_before,
                "programs_after": progs_after,
                "cache_before": cache_before,
                "cache_after": procs.cache_entries(cache_dir()),
                "health": health}

    def stop(self) -> List[Dict]:
        """Reap the children; returns the engine's request log."""
        for c in reversed(self.children):
            c.stop(40.0)
        self.children = []
        if not os.path.exists(getattr(self, "reqlog", "")):
            return []
        with open(self.reqlog) as f:
            return [json.loads(ln) for ln in f if ln.strip()]

    def served_params(self) -> float:
        with open(self.server.log_path, errors="replace") as f:
            for ln in f:
                if "initialized random weights:" in ln:
                    return 1e6 * float(
                        ln.split("weights:")[1].split("M")[0])
        return 0.0


def run_child(name: str, argv: List[str], env: Dict[str, str], logs: str,
              timeout: float) -> Dict:
    """A child whose last stdout line that opens with `{` is its result."""
    child = procs.Child(name, [sys.executable] + argv, env, ROOT, logs)
    try:
        rc = child.wait(timeout)
        lines = [ln for ln in child.log_text().splitlines()
                 if ln.startswith("{")]
        if rc != 0 or not lines:
            raise Fail(f"{name} child exited rc={rc}:\n{child.tail()}")
        return json.loads(lines[-1])
    finally:
        child.stop()


def check(served: Served, groups: List[List[client.Answer]],
          control: bool = False) -> Dict:
    """The served tokens of each group against the plain reference, in
    a child that holds the chip: call after `served.stop()`."""
    spec_path = os.path.join(served.work, "check.json")
    with open(spec_path, "w") as f:
        json.dump({"config_file": served.cell["config_path"],
                   "control": control,
                   "groups": [[{"prompt_ids": a.prompt_ids,
                                "token_ids": a.token_ids()} for a in g]
                              for g in groups]}, f)
    out = run_child("check", [os.path.join(HERE, "check.py"), spec_path],
                    served.env, served.logs, 900.0)
    if served.require_tpu and out["device"]["platform"] != "tpu":
        raise Fail(f"the reference ran on {out['device']}")
    return out


def verdict(served: Served, group: Dict, ref_params: int,
            programs: Dict) -> bool:
    """Print each number compared beside its limit; True when all
    hold. Nothing timed and nothing about how a request ended is among
    them."""
    cfg = served.bench_cfg
    limits = cfg["check"]
    by_name = {p["program"]: p for p in programs["programs"]}

    def holds(prog, need):
        p = by_name[prog]
        return (isinstance(p.get("mosaic_calls"), int)
                and p["mosaic_calls"] >= need and not p["kernel_declines"])

    # `kernels`: programs every cell of the configuration compiles;
    # `kernels_if_compiled`: programs only some traffic reaches (a
    # prefill bucket), held to their kernels where they exist
    kernels_ok = not served.require_tpu or (
        all(prog in by_name and holds(prog, need)
            for prog, need in cfg.get("kernels", {}).items())
        and all(holds(prog, need)
                for prog, need in cfg.get("kernels_if_compiled", {}).items()
                if prog in by_name))
    published = cfg["published_params"]
    compared = [
        ("tokens_compared", group.get("tokens", 0), ">=", 1),
        ("gap_mean", group.get("gap_mean"), "<=", limits["gap_mean_limit"]),
        ("gap_max", group.get("gap_max"), "<=", limits["gap_max_limit"]),
        ("logits_finite", int(bool(group.get("finite"))), ">=", 1),
        ("params_served_vs_published",
         abs(served.served_params() / published - 1), "<=", 0.005),
        ("params_reference_vs_published",
         abs(ref_params / published - 1), "<=", 0.005),
        ("kernels_in_programs", int(kernels_ok), ">=", 1),
    ]
    correct = True
    for name, value, op, limit in compared:
        ok = value is not None and (value <= limit if op == "<="
                                    else value >= limit)
        correct = correct and ok
        say(phase="compare", number=name, value=value, op=op, limit=limit,
            ok=ok)
    return correct


def run(workload: str, seed: int, seconds: float, trace: bool,
        control: bool = False, **served_kw) -> Dict:
    """One run: the result object of the contract (plus `check`, which
    main() drops). Raises Fail where no result can be had."""
    served = Served(workload, seed, trace, **served_kw)
    try:
        ctx = served.window(seed, seconds)
    finally:
        request_log = served.stop()
    ctx["request_log"] = request_log
    answers = ctx["answers"]

    picked = pick_samples(answers, random.Random(seed))
    out = check(served, [picked], control)
    group = out["groups"][0]
    say(phase="check", weights_s=out["weights_s"], check_s=out["check_s"],
        **group)
    correct = verdict(served, group, out["param_count"],
                      ctx["programs_after"])

    ctx["trace"] = None
    if trace:
        try:
            ctx["trace"] = run_child(
                "xtrace", [os.path.join(HERE, "xtrace.py"),
                           served.profile_dir],
                child_env(dict(served.env_extra or {}, JAX_PLATFORMS="cpu")),
                served.logs, 600.0)
        except Fail as e:
            say(phase="trace", error=str(e)[-600:], profile=ctx["profile"])
    kind = "layer_metrics" if trace else "end_to_end"
    metrics = {}
    for m in served.cell["per_layer" if trace else "end_to_end"]:
        value = load_reader(kind, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(served.device, memory_peak_bytes=int(
        ctx["metrics_after"].get("ome_engine_hbm_peak_bytes", 0)))
    result = {"correct": bool(correct), "attempted": len(answers),
              "failed": len(ctx["failed"]), "metrics": metrics,
              "device": dev}
    if trace:
        reduced = ctx["trace"]
        if not reduced or not reduced.get("busy_s"):
            raise Fail("the traced run holds no device operation: "
                       f"{ctx['profile']}")
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    result["check"] = group
    return result


def main(argv=None, **served_kw) -> int:
    """`served_kw` is for the tests (see Served); the command line
    gives none."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), **served_kw)
    except Fail as e:
        print(f"benchmark: no result: {e}", file=sys.stderr, flush=True)
        return 2
    result.pop("check", None)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
