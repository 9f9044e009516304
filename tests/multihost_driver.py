"""Subprocess driver for the 2-process multi-host serving test.

One process of a jax.distributed CPU group: builds the tp=2 sharded
engine over the GLOBAL (cross-process) mesh, then either drives a
scripted request sequence through the leader's ReplicatedEngine or
replays it in the follower loop. The leader writes its token stream to
an output file for the test to compare against a single-process run.

Usage: multihost_driver.py <pid> <nproc> <coord_port> <ctrl_port> <out>
           [mixed <adapter_dir> | spec]

The optional `mixed` mode drives the topology-matrix workload
(json_schema + LoRA adapter + plain request through the real
Scheduler) instead of the raw op script — r4 verdict #10. The `spec`
mode drives the composed StepPlan path (spec-verify × multi-token
chunks × pipelining) through the real Scheduler, exercising the
decode_multi / verify / commit_spec ops on the replicated stream
(docs/step-plan.md).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    pid, nproc = int(sys.argv[1]), int(sys.argv[2])
    coord_port, ctrl_port = sys.argv[3], int(sys.argv[4])
    out_path = sys.argv[5]
    mode = sys.argv[6] if len(sys.argv) > 6 else "script"
    adapter_dir = sys.argv[7] if len(sys.argv) > 7 else None

    import jax
    # each process of the group is one CPU device: set before
    # distributed init.
    # Cross-process computations on the CPU backend need an explicit
    # collectives implementation (the default "none" fails with
    # "Multiprocess computations aren't implemented")
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(f"127.0.0.1:{coord_port}", nproc, pid)
    assert jax.device_count() == nproc, jax.devices()

    import jax.numpy as jnp
    import numpy as np

    from ome_tpu.engine import multihost
    from ome_tpu.engine.sharded import ShardedInferenceEngine
    from ome_tpu.models import llama
    from ome_tpu.models.config import tiny_test

    cfg = tiny_test().replace(dtype=jnp.float32)
    params = jax.tree.map(np.asarray,
                          llama.init_params(jax.random.PRNGKey(0), cfg))
    ekw = dict(max_slots=2, max_seq=64, prefill_buckets=[16])
    if mode == "mixed":
        ekw.update(max_slots=3, lora_slots=2, lora_rank=4,
                   max_seq=128, prefill_buckets=[16, 32])
    eng = ShardedInferenceEngine(params, cfg, tp=nproc, **ekw)

    if pid == 0:
        pub = multihost.OpPublisher(nproc - 1, port=ctrl_port,
                                    host="127.0.0.1")
        reng = multihost.ReplicatedEngine(eng, pub)
        if mode == "mixed":
            tokens = run_mixed(reng, adapter_dir)
        elif mode == "spec":
            tokens = run_spec(reng)
        else:
            tokens = run_script(reng)
        pub.close()
        with open(out_path, "w") as f:
            json.dump(tokens, f)
        return 0
    sub = multihost.OpSubscriber("127.0.0.1", port=ctrl_port)
    rc = multihost.follower_loop(eng, sub)
    sub.close()
    return rc


MIXED_SCHEMA = {
    "type": "object",
    "properties": {"n": {"type": "integer",
                         "minimum": 0, "maximum": 99}},
    "required": ["n"], "additionalProperties": False}


def run_mixed(engine, adapter_dir: str) -> list:
    """The topology-matrix workload: one json_schema-constrained, one
    LoRA-adapter, one plain request through the REAL Scheduler —
    greedy, so every topology must emit identical streams."""
    from ome_tpu.engine.schema import SchemaAutomaton
    from ome_tpu.engine.scheduler import Request, Scheduler
    from ome_tpu.engine.structured import TokenMasker
    from ome_tpu.engine.tokenizer import ByteTokenizer

    engine.register_adapter("styleA", adapter_dir)
    tok = ByteTokenizer()
    sched = Scheduler(engine)
    reqs = [
        Request(prompt_ids=tok.encode("emit n:"), max_new_tokens=14,
                temperature=0.0,
                masker=TokenMasker(
                    tok, automaton=SchemaAutomaton(MIXED_SCHEMA)),
                stop_ids=[tok.eos_id]),
        Request(prompt_ids=tok.encode("styled text"),
                max_new_tokens=10, temperature=0.0, adapter="styleA",
                stop_ids=[]),
        Request(prompt_ids=tok.encode("plain prompt"),
                max_new_tokens=10, temperature=0.0, stop_ids=[]),
    ]
    for r in reqs:
        sched.submit(r)
    for _ in range(400):
        if all(r.done.is_set() for r in reqs):
            break
        sched.step()
    assert all(r.done.is_set() for r in reqs)
    return [list(r.output_ids) for r in reqs]


def run_spec(engine) -> list:
    """Composed StepPlan workload: speculative verify (repetitive
    prompt, so the n-gram drafter actually drafts) × multi-token
    chunks × one-step pipelining, through the REAL Scheduler. Greedy,
    so a group run must match a single-process run byte for byte —
    proving verify / decode_multi / commit_spec replicate."""
    from ome_tpu.engine.scheduler import Request, Scheduler
    from ome_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    sched = Scheduler(engine, spec_tokens=2, steps_per_dispatch=2,
                      pipeline_depth=1)
    assert sched.spec_tokens == 2 and sched.steps_per_dispatch == 2, \
        "composition silently degraded under the replicated engine"
    reqs = [
        Request(prompt_ids=tok.encode("ababababab"),
                max_new_tokens=12, temperature=0.0, stop_ids=[]),
        Request(prompt_ids=tok.encode("xyzxyzxyz"),
                max_new_tokens=10, temperature=0.0, stop_ids=[]),
    ]
    for r in reqs:
        sched.submit(r)
    for _ in range(400):
        if all(r.done.is_set() for r in reqs):
            break
        sched.step()
    assert all(r.done.is_set() for r in reqs)
    return [list(r.output_ids) for r in reqs]


def run_script(eng) -> list:
    """The scripted request mix (mirrors what the Scheduler would do);
    also used by the test for the single-process reference."""
    import numpy as np

    tokens = {0: [], 1: []}
    state = eng.new_state()
    t0, kv0, tl0, b0 = eng.prefill([5, 6, 7, 8])
    state = eng.insert(state, kv0, 0, tl0, t0, b0)
    tokens[0].append(t0)
    t1, kv1, tl1, b1 = eng.prefill([9, 10, 11, 12, 13])
    state = eng.insert(state, kv1, 1, tl1, t1, b1)
    tokens[1].append(t1)
    temp = np.zeros(2, np.float32)
    top_k = np.zeros(2, np.int32)
    top_p = np.ones(2, np.float32)
    for _ in range(6):
        state, toks = eng.decode(state, temp, top_k, top_p)
        host = np.asarray(toks)
        tokens[0].append(int(host[0]))
        tokens[1].append(int(host[1]))
    # constrained steps: a per-step [B, V] mask must ship inside the
    # decode op so followers run the identical masked program
    # (structured outputs under multi-host, VERDICT r3 #4)
    V = eng.cfg.vocab_size
    for step in range(3):
        mask = np.zeros((2, V), dtype=bool)
        mask[:, (step % 3)::3] = True
        state, toks = eng.decode(state, temp, top_k, top_p, mask=mask)
        host = np.asarray(toks)
        tokens[0].append(int(host[0]))
        tokens[1].append(int(host[1]))
    return [tokens[0], tokens[1]]


if __name__ == "__main__":
    sys.exit(main())
