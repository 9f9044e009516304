"""LoRA merge-at-load: PEFT adapter deltas land on the right stacked
leaves with the right scaling/layout, and the merged model actually
changes its outputs."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.models import checkpoint as ck
from ome_tpu.models import llama
from ome_tpu.models.config import ModelConfig
from ome_tpu.models.lora import merge_lora


def _mk_base(tmp_path, D=32, H=4, K=2, Dh=8, F=64, L=2, V=128):
    d = tmp_path / "base"
    d.mkdir()
    hf = {"architectures": ["LlamaForCausalLM"], "vocab_size": V,
          "hidden_size": D, "num_hidden_layers": L,
          "num_attention_heads": H, "num_key_value_heads": K,
          "head_dim": Dh, "intermediate_size": F,
          "max_position_embeddings": 64, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-5, "tie_word_embeddings": False}
    (d / "config.json").write_text(json.dumps(hf))
    rng = np.random.RandomState(0)
    w = lambda *s: rng.randn(*s).astype(np.float32) * 0.02
    T = {"model.embed_tokens.weight": w(V, D),
         "model.norm.weight": np.ones(D, np.float32),
         "lm_head.weight": w(V, D)}
    for i in range(L):
        p = f"model.layers.{i}."
        T.update({
            p + "input_layernorm.weight": np.ones(D, np.float32),
            p + "post_attention_layernorm.weight": np.ones(D, np.float32),
            p + "self_attn.q_proj.weight": w(H * Dh, D),
            p + "self_attn.k_proj.weight": w(K * Dh, D),
            p + "self_attn.v_proj.weight": w(K * Dh, D),
            p + "self_attn.o_proj.weight": w(D, H * Dh),
            p + "mlp.gate_proj.weight": w(F, D),
            p + "mlp.up_proj.weight": w(F, D),
            p + "mlp.down_proj.weight": w(D, F)})
    ck.save_safetensors(str(d / "model.safetensors"), T)
    return str(d)


def _mk_adapter(tmp_path, D=32, H=4, Dh=8, r=4, alpha=8.0):
    a = tmp_path / "adapter"
    a.mkdir()
    (a / "adapter_config.json").write_text(json.dumps(
        {"r": r, "lora_alpha": alpha,
         "target_modules": ["q_proj", "down_proj"]}))
    rng = np.random.RandomState(7)
    A_q = rng.randn(r, D).astype(np.float32) * 0.1
    B_q = rng.randn(H * Dh, r).astype(np.float32) * 0.1
    A_d = rng.randn(r, 64).astype(np.float32) * 0.1
    B_d = rng.randn(D, r).astype(np.float32) * 0.1
    pre = "base_model.model.model.layers.0."
    ck.save_safetensors(str(a / "adapter_model.safetensors"), {
        pre + "self_attn.q_proj.lora_A.weight": A_q,
        pre + "self_attn.q_proj.lora_B.weight": B_q,
        pre + "mlp.down_proj.lora_A.weight": A_d,
        pre + "mlp.down_proj.lora_B.weight": B_d})
    return str(a), (A_q, B_q, A_d, B_d, alpha / r)


def test_merge_applies_exact_delta(tmp_path):
    base = _mk_base(tmp_path)
    adapter, (A_q, B_q, A_d, B_d, scale) = _mk_adapter(tmp_path)
    params, cfg = ck.load_params(base, dtype=jnp.float32,
                                 device_put=False)
    wq_before = np.array(params["layers"]["wq"][0])
    wdown_before = np.array(params["layers"]["w_down"][0])
    wq1_before = np.array(params["layers"]["wq"][1])

    assert merge_lora(params, cfg, adapter) == 2

    # wq lies out-major [H, Dh, D]: the delta [out, in] with heads split
    want_q = wq_before + (scale * (B_q @ A_q)).reshape(4, 8, 32)
    np.testing.assert_allclose(params["layers"]["wq"][0], want_q,
                               atol=1e-5)
    want_d = wdown_before + (scale * (B_d @ A_d)).T
    np.testing.assert_allclose(params["layers"]["w_down"][0], want_d,
                               atol=1e-5)
    # untouched: other layers and modules
    np.testing.assert_array_equal(params["layers"]["wq"][1], wq1_before)


def test_merged_model_changes_output(tmp_path):
    import jax
    base = _mk_base(tmp_path)
    adapter, _ = _mk_adapter(tmp_path)
    tok = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    params, cfg = ck.load_params(base, dtype=jnp.float32,
                                 device_put=False)
    ref, _ = llama.forward(jax.tree.map(jnp.asarray, params), cfg, tok)
    merge_lora(params, cfg, adapter)
    got, _ = llama.forward(jax.tree.map(jnp.asarray, params), cfg, tok)
    assert not np.allclose(np.asarray(got), np.asarray(ref))


def test_incomplete_adapter_rejected(tmp_path):
    base = _mk_base(tmp_path)
    a = tmp_path / "bad"
    a.mkdir()
    (a / "adapter_config.json").write_text(json.dumps({"r": 4}))
    ck.save_safetensors(str(a / "adapter_model.safetensors"), {
        "base_model.model.model.layers.0.self_attn.q_proj.lora_A"
        ".weight": np.zeros((4, 32), np.float32)})
    params, cfg = ck.load_params(base, dtype=jnp.float32,
                                 device_put=False)
    with pytest.raises(ValueError, match="lora_B"):
        merge_lora(params, cfg, str(a))


# -- multi-LoRA serving ----------------------------------------------------


def _mk_named_adapter(tmp_path, name, seed, D=32, H=4, Dh=8, r=4,
                      alpha=8.0):
    a = tmp_path / name
    a.mkdir()
    (a / "adapter_config.json").write_text(json.dumps(
        {"r": r, "lora_alpha": alpha,
         "target_modules": ["q_proj", "o_proj", "up_proj"]}))
    rng = np.random.RandomState(seed)
    T = {}
    for layer in (0, 1):
        pre = f"base_model.model.model.layers.{layer}."
        T[pre + "self_attn.q_proj.lora_A.weight"] = \
            rng.randn(r, D).astype(np.float32) * 0.2
        T[pre + "self_attn.q_proj.lora_B.weight"] = \
            rng.randn(H * Dh, r).astype(np.float32) * 0.2
        T[pre + "self_attn.o_proj.lora_A.weight"] = \
            rng.randn(r, H * Dh).astype(np.float32) * 0.2
        T[pre + "self_attn.o_proj.lora_B.weight"] = \
            rng.randn(D, r).astype(np.float32) * 0.2
        T[pre + "mlp.up_proj.lora_A.weight"] = \
            rng.randn(r, D).astype(np.float32) * 0.2
        T[pre + "mlp.up_proj.lora_B.weight"] = \
            rng.randn(64, r).astype(np.float32) * 0.2
    ck.save_safetensors(str(a / "adapter_model.safetensors"), T)
    return str(a)


def _greedy(engine, prompt, steps=8, adapter=None):
    """Drive prefill+insert+decode directly; returns the token list."""
    state = engine.new_state()
    kw = {} if adapter is None else {"adapter": adapter}
    tok, kv, tl, b = engine.prefill(prompt, **kw)
    state = engine.insert(state, kv, 0, tl, tok, b, **kw)
    out = [tok]
    temp = np.zeros(engine.max_slots, np.float32)
    top_k = np.zeros(engine.max_slots, np.int32)
    top_p = np.ones(engine.max_slots, np.float32)
    for _ in range(steps):
        state, toks = engine.decode(state, temp, top_k, top_p)
        out.append(int(np.asarray(toks)[0]))
    return out


def test_multi_lora_matches_merged_baselines(tmp_path):
    """One engine serving base + 2 adapters concurrently must produce
    EXACTLY the tokens of per-adapter merged engines (VERDICT r3 #5)."""
    import jax

    from ome_tpu.engine.core import InferenceEngine
    base = _mk_base(tmp_path)
    a1 = _mk_named_adapter(tmp_path, "a1", seed=11)
    a2 = _mk_named_adapter(tmp_path, "a2", seed=22)

    def merged_engine(adapter_dir=None):
        params, cfg = ck.load_params(base, dtype=jnp.float32,
                                     device_put=False)
        if adapter_dir:
            merge_lora(params, cfg, adapter_dir)
        params = jax.tree.map(jnp.asarray, params)
        return InferenceEngine(params, cfg, max_slots=4,
                               max_seq=32, prefill_buckets=[8])

    prompt = [5, 6, 7, 8]
    want_base = _greedy(merged_engine(), prompt)
    want_a1 = _greedy(merged_engine(a1), prompt)
    want_a2 = _greedy(merged_engine(a2), prompt)
    assert want_a1 != want_base or want_a2 != want_base

    params, cfg = ck.load_params(base, dtype=jnp.float32,
                                 device_put=False)
    params = jax.tree.map(jnp.asarray, params)
    eng = InferenceEngine(params, cfg, max_slots=4, max_seq=32,
                          prefill_buckets=[8], lora_slots=3,
                          lora_rank=8)
    eng.register_adapter("a1", a1)
    eng.register_adapter("a2", a2)
    assert eng.adapter_names == ["a1", "a2"]

    assert _greedy(eng, prompt) == want_base
    assert _greedy(eng, prompt, adapter="a1") == want_a1
    assert _greedy(eng, prompt, adapter="a2") == want_a2

    # concurrent slots: all three in ONE decode batch, interleaved
    state = eng.new_state()
    reqs = [(None, want_base), ("a1", want_a1), ("a2", want_a2)]
    for slot, (ad, _) in enumerate(reqs):
        kw = {} if ad is None else {"adapter": ad}
        tok, kv, tl, b = eng.prefill(prompt, **kw)
        state = eng.insert(state, kv, slot, tl, tok, b, **kw)
    outs = [[w[0]] for _, w in reqs]
    temp = np.zeros(4, np.float32)
    top_k = np.zeros(4, np.int32)
    top_p = np.ones(4, np.float32)
    for _ in range(8):
        state, toks = eng.decode(state, temp, top_k, top_p)
        for i in range(3):
            outs[i].append(int(np.asarray(toks)[i]))
    for (ad, want), got in zip(reqs, outs):
        assert got == want, f"adapter {ad}: {got} != {want}"

    # hot swap: unregister then register a DIFFERENT adapter under the
    # same name — no recompilation (same shapes), new deltas apply.
    # Slots must be released first: unload refuses while any slot
    # still references the adapter (r4 advisor — a reused slot id
    # would silently flip in-flight sequences to another adapter)
    with pytest.raises(ValueError, match="in-flight"):
        eng.unregister_adapter("a1")
    for slot in range(3):
        eng.free_slot(slot)
    eng.unregister_adapter("a1")
    with pytest.raises(ValueError, match="unknown adapter"):
        eng.adapter_id("a1")
    eng.register_adapter("a1", a2)  # a1 now points at a2's weights
    assert _greedy(eng, prompt, adapter="a1") == want_a2


def test_lora_rank_cap_enforced(tmp_path):
    import jax

    from ome_tpu.engine.core import InferenceEngine
    base = _mk_base(tmp_path)
    a1 = _mk_named_adapter(tmp_path, "big", seed=3, r=8)
    params, cfg = ck.load_params(base, dtype=jnp.float32,
                                 device_put=False)
    params = jax.tree.map(jnp.asarray, params)
    eng = InferenceEngine(params, cfg, max_slots=2, max_seq=32,
                          prefill_buckets=[8], lora_slots=1,
                          lora_rank=4)
    with pytest.raises(ValueError, match="exceeds"):
        eng.register_adapter("big", a1)


def test_unknown_adapter_fails_request_not_scheduler(tmp_path):
    """A request naming an unloaded adapter (racing a hot unload) must
    fail alone — the scheduler stays healthy and keeps serving."""
    import jax

    from ome_tpu.engine.core import InferenceEngine
    from ome_tpu.engine.scheduler import Request, Scheduler
    base = _mk_base(tmp_path)
    params, cfg = ck.load_params(base, dtype=jnp.float32,
                                 device_put=False)
    params = jax.tree.map(jnp.asarray, params)
    eng = InferenceEngine(params, cfg, max_slots=2, max_seq=32,
                          prefill_buckets=[8], lora_slots=1)
    sched = Scheduler(eng)
    bad = sched.submit(Request(prompt_ids=[1, 2, 3], max_new_tokens=4,
                               adapter="ghost"))
    ok = sched.submit(Request(prompt_ids=[1, 2, 3], max_new_tokens=4))
    while not (bad.done.is_set() and ok.done.is_set()):
        sched.step()
    assert bad.finish_reason == "error"
    assert ok.finish_reason in ("stop", "length")
    assert sched.healthy


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_per_slot_lora_on_out_major_projections_matches_the_parent_form(
        tmp_path, dtype):
    """Per-slot LoRA on `wq` (stored [L, heads, Dh, D] since PR 41; the
    factors stay [r, K] / [r, N]): one engine serving the base model
    and two adapters in one batch gives, slot by slot, the logits of
    the same engine built on the parent's form of the projections
    (`_parent_proj.parent_form`): to the bit in bfloat16, to 1e-5 in
    float32."""
    import jax

    from _parent_proj import parent_form
    from ome_tpu.engine.core import InferenceEngine
    base = _mk_base(tmp_path)
    a1 = _mk_named_adapter(tmp_path, "a1", seed=11)
    a2 = _mk_named_adapter(tmp_path, "a2", seed=22)
    dt = jnp.dtype(dtype)

    def logits():
        params, cfg = ck.load_params(base, dtype=dt, device_put=False)
        params = jax.tree.map(jnp.asarray, params)
        eng = InferenceEngine(params, cfg, max_slots=4, max_seq=32,
                              prefill_buckets=[8], lora_slots=3,
                              lora_rank=8)
        eng.register_adapter("a1", a1)
        eng.register_adapter("a2", a2)
        toks = jnp.asarray([[5, 6, 7, 8]] * 3, jnp.int32)
        lg, _ = llama.forward(eng.params, cfg, toks,
                              adapter_ids=jnp.asarray([0, 1, 2]))
        return np.asarray(lg.astype(jnp.float32))

    got = logits()
    with parent_form():
        want = logits()
    assert np.abs(got[0] - got[1]).max() > 1e-3      # the adapters act
    assert np.abs(got[1] - got[2]).max() > 1e-3
    if dtype == "bfloat16":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, atol=1e-5)
