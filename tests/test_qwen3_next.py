"""Qwen3-Next on the serve path: Gated DeltaNet layers with per-slot
recurrent state beside a KV slab of the full-attention layers only,
and an expert layer that holds a share of its experts.

Small sizes with the true ratios (Hv = 2 Hk, rotary on a quarter of
the head, 3 DeltaNet layers to 1 full, 2 periods): the program in
float32 against the benchmark's plain reference
(benchmark/reference/hybrid_gdn_moe.py, which imports nothing of the
program) and against `transformers`' own Qwen3NextForCausalLM; the
chunked form of the recurrence against the step form; a right-padded
prompt against the same prompt unpadded; slots of different length
together against each alone; the four EP-4 shares adding up to the
uncut layer; what start-up refuses.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.engine import core
from ome_tpu.engine.core import InferenceEngine
from ome_tpu.models import checkpoint as ck
from ome_tpu.models import gdn, llama
from ome_tpu.models.config import ModelConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import hybrid_gdn_moe as ref  # noqa: E402

from _parent_proj import stored  # noqa: E402

HF = dict(
    architectures=["Qwen3NextForCausalLM"], model_type="qwen3_next",
    hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, vocab_size=256,
    full_attention_interval=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    partial_rotary_factor=0.25, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=16,
    num_experts_per_tok=4, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=1e7, tie_word_embeddings=False, decoder_sparse_step=1,
    mlp_only_layers=[], intermediate_size=128,
    max_position_embeddings=512)
# the chip's share: experts 4..11 of 16 held, the router 16 wide
CUT = dict(HF, num_experts=8, ep_num_experts_total=16,
           ep_expert_offset=4)


def _cfg(hf):
    return ModelConfig.from_hf_config(hf).replace(
        dtype=jnp.float32, moe_impl="ragged")


def _params(cfg):
    return jax.jit(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))()


@pytest.fixture(scope="module")
def cut():
    cfg = _cfg(CUT)
    return cfg, _params(cfg), ref.init_weights(CUT, jnp.float32)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, HF["vocab_size"], n)


# -- the program's weights and logits are the reference's ---------------


def test_reference_makes_the_served_weights(cut):
    cfg, p, w = cut
    assert set(w["full"]) == set(p["layers"])
    assert set(w["linear"]) == set(p["linear_layers"])
    for mine, theirs in ((p["layers"], w["full"]),
                         (p["linear_layers"], w["linear"]),
                         (p, {k: w[k] for k in ("embed", "lm_head",
                                                "final_norm")})):
        for name, leaf in theirs.items():
            np.testing.assert_array_equal(
                np.asarray(mine[name]), np.asarray(stored(name, leaf)),
                err_msg=name)
    assert llama.param_count(p) == sum(
        x.size for x in jax.tree.leaves(w))


@pytest.mark.parametrize("n", [7, 64, 150])
def test_forward_matches_the_reference(cut, n):
    """One full pass, the chunked recurrence (chunks of 64, so 150
    spans three and a ragged tail) against the reference's token-by-
    token scan."""
    cfg, p, w = cut
    toks = _tokens(n)
    lg, _ = llama.forward(p, cfg, jnp.asarray(toks[None]))
    want = ref.logits(w, CUT, toks, 0, n)
    assert float(want.std()) > 0.05
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(want),
                               atol=2e-5)


def test_prefill_then_decode_matches_one_full_pass(cut):
    """Prefill of a right-padded bucket, then one token at a time
    through the KV slab of the full layers and the recurrent state of
    the others, on logits against the reference's one pass."""
    cfg, p, w = cut
    toks, n0, bucket = _tokens(50), 37, 64
    want = np.asarray(ref.logits(w, CUT, toks, 0, len(toks)))
    cache = llama.KVCache.create(cfg, 1, 128)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n0] = toks[:n0]
    true_len = jnp.asarray([n0], jnp.int32)
    lg, cache = llama.forward(p, cfg, jnp.asarray(padded), cache=cache,
                              logits_at=true_len - 1,
                              valid_len=true_len)
    np.testing.assert_allclose(np.asarray(lg[0, 0]), want[n0 - 1],
                               atol=2e-5)
    # the slab hides the padded tail behind the slot's length
    cache = llama.KVCache(k=cache.k, v=cache.v, index=true_len,
                          rec=cache.rec)
    for t in range(n0, len(toks)):
        lg, cache = llama.forward(p, cfg, jnp.asarray(toks[None, t:t + 1]),
                                  cache=cache)
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[t],
                                   atol=2e-5, err_msg=f"row {t}")


def test_transformers_logits_match(tmp_path):
    """The layer equations and the checkpoint's layouts (q|gate per
    head, qkvz and ba per key head) against the published model."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    try:
        hf_cfg = transformers.Qwen3NextConfig(
            **{k: v for k, v in HF.items()
               if k not in ("architectures", "model_type")},
            pad_token_id=0, bos_token_id=1, eos_token_id=2)
    except AttributeError:
        pytest.skip("transformers has no Qwen3Next")
    torch.manual_seed(0)
    model = transformers.AutoModelForCausalLM.from_config(hf_cfg).eval()
    with torch.no_grad():
        # the published init leaves the zero-centred norms at zero and
        # the decay at once-forgetting: move both so that they count
        for name, t in model.named_parameters():
            if name.endswith("norm.weight") or "A_log" in name \
                    or "dt_bias" in name:
                t.copy_(torch.randn_like(t) * 0.3
                        - (2.0 if "A_log" in name else 0.0))
    d = str(tmp_path / "model")
    model.save_pretrained(d, safe_serialization=True)
    params, cfg = ck.load_params(d, dtype=jnp.float32)
    assert cfg.is_hybrid and cfg.kv_cache_layers == 2
    toks = _tokens(70, seed=3)[None]
    lg, _ = llama.forward(params, cfg.replace(moe_impl="ragged"),
                          jnp.asarray(toks))
    with torch.no_grad():
        want = model(torch.tensor(toks, dtype=torch.long)).logits
    np.testing.assert_allclose(np.asarray(lg), want.numpy(), atol=5e-4,
                               rtol=1e-3)
    # a cut config loads its share of the same checkpoint: the held
    # experts' rows, the router whole
    with open(os.path.join(d, "config.json")) as f:
        hf = json.load(f)
    hf.update(num_experts=8, ep_num_experts_total=16, ep_expert_offset=4,
              vocab_size=128)
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(hf, f)
    share, scfg = ck.load_params(d, dtype=jnp.float32)
    assert scfg.router_width == 16 and scfg.expert_offset == 4
    for block in ("layers", "linear_layers"):
        np.testing.assert_array_equal(
            share[block]["we_up"], params[block]["we_up"][:, 4:12])
        np.testing.assert_array_equal(share[block]["router"],
                                      params[block]["router"])
    np.testing.assert_array_equal(share["embed"], params["embed"][:128])
    assert share["lm_head"].shape == (64, 128)


# -- the recurrence: two forms, one result -----------------------------


def _gdn_inputs(B, T, H=4, dk=16, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gdn.l2norm(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = gdn.l2norm(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S = jax.random.normal(ks[5], (B, H, dk, dv)) * 0.1
    return q, k, v, g, beta, S


def _by_steps(q, k, v, g, beta, S, valid=None):
    out = []
    for t in range(q.shape[1]):
        o, S = gdn.step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                        S, None if valid is None else valid[:, t])
        out.append(o)
    return jnp.stack(out, 1), S


@pytest.mark.parametrize("T,chunk", [(5, 64), (64, 64), (130, 64),
                                     (96, 32)])
def test_chunked_form_is_the_step_form(T, chunk):
    args = _gdn_inputs(2, T)
    o_c, S_c = gdn.chunked(*args, chunk=chunk)
    o_s, S_s = _by_steps(*args)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_s),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(S_c), np.asarray(S_s),
                               atol=2e-5)


@pytest.mark.parametrize("form", ["chunked", "step"])
def test_invalid_positions_change_nothing(form):
    """A validity mask: the state after 70 positions of which rows 0
    and 1 have 41 and 70 valid is the state after exactly those."""
    q, k, v, g, beta, S = _gdn_inputs(2, 70, seed=1)
    valid = jnp.arange(70)[None, :] < jnp.asarray([41, 70])[:, None]
    run = gdn.chunked if form == "chunked" else _by_steps
    o, S_out = run(q, k, v, g, beta, S, valid)
    for b, n in enumerate((41, 70)):
        o_b, S_b = _by_steps(*(a[b:b + 1, :n] for a in (q, k, v, g, beta)),
                             S[b:b + 1])
        np.testing.assert_allclose(np.asarray(S_out[b]),
                                   np.asarray(S_b[0]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(o[b, :n]),
                                   np.asarray(o_b[0]), atol=2e-5)


def test_conv_tail_is_the_last_inputs_before_the_valid_length():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    tail0 = jnp.zeros((2, 3, 6))
    y, tail = gdn.conv_seq(x, tail0, w, jnp.asarray([9, 20]))
    np.testing.assert_array_equal(tail[0], x[0, 6:9])
    np.testing.assert_array_equal(tail[1], x[1, 17:20])
    # one token at a time from there gives the sequence's own outputs
    y1, t1 = gdn.conv_seq(x[:1, 9:10], tail[:1], w)
    np.testing.assert_allclose(np.asarray(y1[0, 0]), np.asarray(y[0, 9]),
                               atol=1e-6)
    _, held = gdn.conv_seq(x[:1, 9:10], tail[:1], w, jnp.asarray([0]))
    np.testing.assert_array_equal(held, tail[:1])
    np.testing.assert_array_equal(t1[0], x[0, 7:10])


# -- the engine: a slot owns rows and state ----------------------------


@pytest.fixture(scope="module")
def engine(cut):
    cfg, p, _ = cut
    return InferenceEngine(p, cfg, max_slots=3, max_seq=256)


def _greedy(n):
    return (np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32))


@pytest.mark.parametrize("n", [33, 64, 70])
def test_padded_prompt_equals_unpadded(engine, n):
    """A prompt right-padded to its bucket (64 or 128) hands back the
    state, and then the next logits' token, of the same prompt run
    unpadded: pad rows leave S and the conv tail untouched."""
    cfg, p = engine.cfg, engine.params
    ids = [int(t) for t in _tokens(n, seed=n)]
    tok, kv, true_len, bucket = engine.prefill(ids)
    assert (true_len, len(kv)) == (n, 3) and bucket >= n
    cache = llama.KVCache.create(cfg, 1, n)
    lg, exact = llama.forward(p, cfg, jnp.asarray([ids]), cache=cache)
    assert tok == int(lg[0, -1].argmax())
    for name in ("S", "conv"):
        np.testing.assert_allclose(
            np.asarray(kv[2][name]), np.asarray(exact.rec[name]),
            atol=1e-5, err_msg=name)
    held = np.asarray(kv[0][:, :, :n])    # the engine's merged rows
    np.testing.assert_allclose(
        held, np.asarray(exact.k).reshape(held.shape), atol=1e-5)


def test_two_slots_of_different_length_decode_as_each_alone(cut, engine):
    """Slots 0 and 2 at lengths 70 and 33 (slot 1 free, its state
    running on whatever it holds) through single steps, a multi-token
    chunk that freezes one of them midway, and single steps again:
    every served token is the reference's best for that sequence."""
    _, _, w = cut
    st = engine.new_state()
    seqs, first = {}, {0: 70, 2: 33}
    for slot, n in first.items():
        ids = [int(t) for t in _tokens(n, seed=slot)]
        tok, kv, true_len, bucket = engine.prefill(ids)
        st = engine.insert(st, kv, slot, true_len, tok, bucket)
        seqs[slot] = ids + [tok]
    for _ in range(3):
        st, toks = engine.decode(st, *_greedy(3))
        for s in seqs:
            seqs[s].append(int(np.asarray(toks)[s]))
    st, toks, adv = engine.decode_multi(
        st, *_greedy(3), 4, np.asarray([4, 0, 2], np.int32),
        np.full((3, 1), -1, np.int32))
    assert list(np.asarray(adv)) == [4, 0, 2]
    for s in seqs:
        seqs[s] += [int(t) for t in np.asarray(toks)[s, :np.asarray(adv)[s]]]
    st, toks = engine.decode(st, *_greedy(3))
    for s in seqs:
        seqs[s].append(int(np.asarray(toks)[s]))
    for s, ids in seqs.items():
        want = ref.logits(w, CUT, np.asarray(ids[:-1]), 0, len(ids) - 1)
        best = [int(t) for t in np.asarray(want.argmax(-1))[first[s] - 1:]]
        assert ids[first[s]:] == best, f"slot {s}"
    counts = engine.moe_counters()
    # 8 expert layers a step; 3 + 4 + 1 steps
    assert counts["layer_steps"] == 8 * 8
    assert 0 < counts["experts_hit"] <= 8 * counts["layer_steps"]
    assert counts["experts_hit"] <= counts["pairs"] \
        <= 3 * 4 * counts["layer_steps"]
    assert engine.moe_counters() == counts       # read twice: no drift


@pytest.mark.parametrize("merged", [True, False],
                         ids=["merged", "heads_apart"])
def test_a_decode_step_changes_its_own_rows_and_no_others(cut, merged):
    """The full layers' slabs ride the layer scan's carry and a step
    writes its rows in place (`_hybrid_scan`): a cached decode step
    through `llama.forward` at per-slot lengths that differ changes,
    in `k` and in `v`, exactly the rows [layer, slot, index[slot]],
    in both full layers, and every other row of every layer comes
    back equal to the bit."""
    cfg, p, _ = cut
    B, S = 3, 48
    index = np.asarray([5, 0, 31], np.int32)
    rng = np.random.RandomState(7)
    cache = llama.KVCache.create(cfg, B, S, merged=merged)
    assert cache.k.shape[0] == cfg.kv_cache_layers == 2
    before = {n: rng.standard_normal(getattr(cache, n).shape)
              .astype(np.float32) for n in ("k", "v")}
    cache = llama.KVCache(
        k=jnp.asarray(before["k"]), v=jnp.asarray(before["v"]),
        index=jnp.asarray(index),
        rec=jax.tree.map(lambda a: jnp.asarray(
            rng.standard_normal(a.shape) * 0.1, a.dtype), cache.rec))
    toks = jnp.asarray(_tokens(B, seed=3)[:, None], jnp.int32)
    _, after = llama.forward(p, cfg, toks, cache=cache)
    np.testing.assert_array_equal(np.asarray(after.index), index + 1)
    written = np.zeros((cfg.kv_cache_layers, B, S), bool)
    written[:, np.arange(B), index] = True
    for n in ("k", "v"):
        got = np.asarray(getattr(after, n))
        assert got.shape == before[n].shape
        changed = (got != before[n]).reshape(written.shape + (-1,))
        # a fresh row differs from the noise it replaced in every
        # lane; no other row differs in any
        assert changed[written].all(), n
        assert not changed[~written].any(), n


def test_a_slots_state_is_counted_and_dense_models_have_none(engine):
    cfg = engine.cfg
    per_slot = cfg.linear_layers * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert engine.state_bytes() == 3 * per_slot
    st = engine.new_state()
    assert st.k.shape[0] == cfg.kv_cache_layers == 2
    assert st.rec["S"].shape == (6, 3, 4, 16, 16)
    assert st.rec["S"].dtype == jnp.float32
    assert engine.kv_row_bytes() == 2 * 2 * (32 + 32) * 4
    from ome_tpu.perf.hbm import HbmAccountant
    from ome_tpu.telemetry.registry import Registry
    part = HbmAccountant.for_engine(engine, Registry()).update(engine)
    assert part["recurrent_state"] == engine.state_bytes()
    from ome_tpu.models.config import tiny_test
    dense_cfg = tiny_test().replace(dtype=jnp.float32)
    dense = InferenceEngine(
        llama.init_params(jax.random.PRNGKey(0), dense_cfg), dense_cfg,
        max_slots=2, max_seq=64)
    st = dense.new_state()
    assert st.rec is None and st.moe_stats is None
    assert dense.state_bytes() == 0 and dense.moe_counters() is None
    assert len(jax.tree.leaves(st)) == 5     # k, v, lengths, tokens, adapters


# -- the expert layer that is told which experts it holds --------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """EP-4 over 16 experts: the routed parts the four shares give,
    with the shared expert (which every chip computes alike) counted
    once, equal the uncut reference's whole layer."""
    w = ref.init_weights(HF, jnp.float32)          # all 16 held
    lp = {k: v[1] for k, v in w["linear"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 64))
    want = ref._moe(x.reshape(18, 64), w["linear"], 1, eps=1e-6,
                    top_k=4, lo=0, int8=False).reshape(2, 9, 64)
    uncut = _cfg(HF)
    h = llama.block_norm(x, lp, "mlp_norm", uncut)
    routed, hits = 0.0, 0
    for r in range(4):
        cfg = uncut.replace(num_experts=4, num_experts_total=16,
                            expert_offset=4 * r)
        held = dict(lp, **{k: lp[k][4 * r:4 * r + 4]
                           for k in ("we_gate", "we_up", "we_down")})
        part, (hit, pairs) = llama.moe_mlp_ragged(h, held, cfg,
                                                  with_stats=True)
        routed = routed + part
        hits += int(pairs)
    assert hits == 18 * 4          # every pair landed on one share
    whole = llama.moe_mlp(h, lp, uncut)
    shared = whole - llama.moe_mlp_ragged(h, lp, uncut)
    np.testing.assert_allclose(np.asarray(x + routed + shared),
                               np.asarray(want), atol=2e-6)
    np.testing.assert_allclose(np.asarray(x + whole), np.asarray(want),
                               atol=2e-6)


def test_pairs_routed_to_absent_experts_take_no_group():
    """Three quarters of the pairs of an EP-4 share are routed
    elsewhere: the grouped matmuls' group sizes count the local
    quarter only (they used to ride along as expert 0's rows)."""
    cfg = _cfg(CUT)
    p = _params(cfg)
    lp = {k: v[0] for k, v in p["linear_layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 64))
    weights, idx = llama._route(x, lp, cfg)
    local = int(((idx >= 4) & (idx < 12)).sum())
    _, (hit, pairs) = llama.moe_mlp_ragged(x, lp, cfg, with_stats=True)
    assert int(pairs) == local < 40 * 4
    assert int(hit) == len(set(np.asarray(idx)[(np.asarray(idx) >= 4)
                                               & (np.asarray(idx) < 12)]))
    with pytest.raises(ValueError, match="ragged"):
        llama.moe_mlp(x, lp, cfg.replace(moe_impl="dense"))


def test_int8_quantization_covers_both_blocks(cut):
    cfg, p, _ = cut
    from ome_tpu.models.quant import QTensor, quantize_params
    q = quantize_params(p, mode="int8")
    for block, names in (("layers", ("wq", "w_ogate", "we_up")),
                         ("linear_layers", ("w_qkv", "w_z", "w_lin_out",
                                            "we_down", "ws_gate"))):
        for name in names:
            assert isinstance(q[block][name], QTensor), name
    assert not isinstance(q["linear_layers"]["conv_w"], QTensor)
    toks = jnp.asarray(_tokens(40)[None])
    a, _ = llama.forward(p, cfg, toks)
    b, _ = llama.forward(q, cfg, toks)
    assert 0 < float(jnp.abs(a - b).max()) < 0.2 * float(a.std())


# -- what start-up refuses, with the reason -----------------------------


@pytest.mark.parametrize("kw,reason", [
    (dict(kv_block=128), "paged pool"),
    (dict(prefix_cache_bytes=1 << 20), "recurrent state at the prefix"),
    (dict(prefix_host_bytes=1 << 20), "recurrent state at the prefix"),
    (dict(lora_slots=2), "DeltaNet mixer"),
])
def test_engine_refuses_what_assumes_rows_are_all_a_slot_owns(cut, kw,
                                                              reason):
    cfg, p, _ = cut
    with pytest.raises(ValueError, match=reason):
        InferenceEngine(p, cfg, max_slots=2, max_seq=128, **kw)


@pytest.mark.parametrize("flags,reason", [
    (["--kv-block", "128"], "--kv-block"),
    (["--prefix-cache-mb", "64"], "--prefix-cache-mb"),
    (["--spec-tokens", "4"], "rolled back"),
    (["--lora-slots", "2"], "--lora-slots"),
    (["--disaggregation-mode", "prefill"], "PD transfer"),
    (["--journal", "/tmp/j"], "--journal"),
    (["--tp", "2"], "--tp"),
])
def test_serve_stops_at_start_up_with_the_reason(tmp_path, flags, reason):
    from ome_tpu.engine import serve
    with open(tmp_path / "config.json", "w") as f:
        json.dump(CUT, f)
    base = ["--model-dir", str(tmp_path), "--random-weights",
            "--dtype", "float32", "--max-slots", "2", "--max-seq", "128"]
    if "--prefix-cache-mb" not in flags:
        base += ["--prefix-cache-mb", "0"]
    args = serve.build_parser().parse_args(base + flags)
    with pytest.raises(SystemExit) as e:
        serve.load_engine(args)
    assert reason in str(e.value) and "recurrent" in str(e.value)


def test_serve_builds_the_engine_and_verify_is_refused(tmp_path):
    from ome_tpu.engine import serve
    with open(tmp_path / "config.json", "w") as f:
        json.dump(CUT, f)
    args = serve.build_parser().parse_args(
        ["--model-dir", str(tmp_path), "--random-weights", "--dtype",
         "float32", "--max-slots", "2", "--max-seq", "128",
         "--prefix-cache-mb", "0"])
    eng = serve.load_engine(args)
    assert eng.cfg.is_hybrid and eng.cfg.moe_impl == "ragged"
    assert eng.cfg.router_width == 16 and eng.cfg.num_experts == 8
    with pytest.raises(ValueError, match="rolled back"):
        eng.verify(eng.new_state(), np.zeros((2, 2), np.int32),
                   np.zeros(2, np.int32), *_greedy(2))
    assert core.slot_state_refusals(
        ModelConfig(), kv_block=128, spec_tokens=4) == []


# -- the benchmark's configuration -------------------------------------


def test_the_benchmarks_configuration_counts_what_it_says():
    """Every published width kept, the three cuts as listed, and the
    held parameter count of the file within 0.5 % of the program's and
    of the reference's."""
    path = os.path.join(ROOT, "benchmark", "configs",
                        "qwen3-next-80b-a3b-ep4.json")
    with open(path) as f:
        file = json.load(f)
    hf = {k: v for k, v in file.items()
          if k not in ("source", "reduced", "assumed", "benchmark")}
    cfg = ModelConfig.from_hf_config(hf)
    assert (cfg.num_layers, cfg.num_experts, cfg.router_width,
            cfg.vocab_size) == (12, 128, 512, 37984)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_heads,
            cfg.num_kv_heads, cfg.experts_per_token,
            cfg.moe_intermediate_size) == (2048, 256, 16, 2, 10, 512)
    assert cfg.linear_conv_dim == 8192 and cfg.linear_layers == 9
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    served = sum(x.size for x in jax.tree.leaves(shapes))
    theirs = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: ref.init_weights(hf))))
    published = file["benchmark"]["published_params"]
    assert served == theirs
    assert abs(served / published - 1) < 0.005
    assert sorted(file["reduced"]) == ["num_experts",
                                       "num_hidden_layers", "vocab_size"]
