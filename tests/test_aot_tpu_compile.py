"""Main-path kernels through the chip's own compiler, without the chip.

libtpu is installed here and compiles for a TPU that is described, not
attached (on-chip-measurement guide, section 2). Interpret mode cannot
see what Mosaic refuses — a scale block of 10 sublanes, a kernel GSPMD
cannot partition — so the kernels the serve path runs at Qwen3-4B
widths (32 heads / 8 KV heads, head_dim 128, hidden 2560, MLP 9728,
KV block 128) are compiled here for a described v5e:2x2. A compile
that passes is not a chip run; it says the kernel is accepted, nothing
about its results or its speed.
"""

import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ome_tpu import device
from ome_tpu.models.quant import QTensor
from ome_tpu.ops import attention as attn_ops
from ome_tpu.ops import flash, int4_matmul, paged

B, H, K, D, BS = 16, 32, 8, 128, 128   # slots, heads, KV heads, dims


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip (the next one
    warns and recompiles), so the cache is off around this module."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return struct


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("pool_dtype", ["bf16", "int8"])
def test_paged_flash_decode(one_chip, pool_dtype):
    """The kernel handed the whole pool and a traced layer index."""
    layers, n_blocks, max_blocks = 4, 257, 16
    dt = jnp.int8 if pool_dtype == "int8" else jnp.bfloat16
    pool = one_chip((layers, n_blocks, BS, K, D), dt)
    scale = (one_chip((layers, n_blocks, K, BS), jnp.float32)
             if pool_dtype == "int8" else None)

    def f(q, kp, vp, table, kv_len, layer, ks, vs):
        out = paged.paged_flash_decode(q, kp, vp, table, kv_len, layer,
                                       k_scale=ks, v_scale=vs)
        assert out is not None, "kernel declined the serve-path shape"
        return out

    c = _compile(f, one_chip((B, 1, H, D), jnp.bfloat16), pool, pool,
                 one_chip((B, max_blocks), jnp.int32),
                 one_chip((B,), jnp.int32), one_chip((), jnp.int32),
                 scale, scale)
    assert "tpu_custom_call" in c.as_text()
    # read in place: no layer's pool is sliced out for the kernel
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20


def test_flash_decode_dense_slab(one_chip):
    S = 4096
    kv = one_chip((B, S, K * D), jnp.bfloat16)  # a row's heads merged

    def f(q, k, v, lo, hi):
        out = flash._flash_decode(q, k, v, lo, hi, D ** -0.5, None,
                                  False)
        assert out is not None
        return out

    c = _compile(f, one_chip((B, 1, H, D), jnp.bfloat16), kv, kv,
                 one_chip((B,), jnp.int32), one_chip((B,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("S", [512, 2048])
def test_flash_prefill(one_chip, S):
    kv = one_chip((1, S, K, D), jnp.bfloat16)

    def f(q, k, v, base, kv_hi):
        out = flash._flash_prefill(q, k, v, base, kv_hi, D ** -0.5,
                                   None, None, False)
        assert out is not None
        return out

    c = _compile(f, one_chip((1, S, H, D), jnp.bfloat16), kv, kv,
                 one_chip((1,), jnp.int32), one_chip((1,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("S,heads,kv_heads,dim,window,key_steps", [
    # trinity-mini-ep4.long-doc's 16 384 bucket, a global and a window
    # layer; qwen3-next-80b-a3b-ep4.long-batch's 4096 bucket (bq 128)
    (16384, 32, 4, 128, None, 32),
    (16384, 32, 4, 128, 2048, 6),
    (4096, 16, 2, 256, None, 8),
    # smallthinker-21b-a3b-ep4.long-decode's buckets: 7 heads a KV
    # head (a query block of 896 lanes), window 4096
    (16384, 28, 4, 128, None, 32),
    (16384, 28, 4, 128, 4096, 10),
    (8192, 28, 4, 128, 4096, 10),
])
def test_flash_prefill_at_the_cells_shapes(one_chip, monkeypatch, S,
                                           heads, kv_heads, dim, window,
                                           key_steps):
    """Both bodies (a whole block's, with no mask arithmetic, and an
    edge block's) inside the VMEM limit at the slab cells' widths, and
    a window layer's grid trimmed to the key blocks a query block's
    windows can reach."""
    grids = []
    call = flash.pl.pallas_call

    def spy(kernel, *, grid_spec, **kw):
        grids.append(grid_spec.grid)
        return call(kernel, grid_spec=grid_spec, **kw)

    monkeypatch.setattr(flash.pl, "pallas_call", spy)
    flash._prefill_call.clear_cache()    # trace the call anew, spied on
    kv = one_chip((1, S, kv_heads, dim), jnp.bfloat16)

    def f(q, k, v, base, kv_hi):
        out = flash._flash_prefill(q, k, v, base, kv_hi, dim ** -0.5,
                                   None, window, False)
        assert out is not None
        return out

    c = _compile(f, one_chip((1, S, heads, dim), jnp.bfloat16), kv, kv,
                 one_chip((1,), jnp.int32), one_chip((1,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()
    bq = 128 if dim == 256 else 256
    assert grids == [(1, kv_heads, S // bq, key_steps)]
    kinds = flash.prefill_block_kinds(S, S, kv_heads, heads // kv_heads,
                                      dim, 0, S, window)
    assert kinds["whole"] and kinds["edge"]


@pytest.mark.parametrize("k,n", [(2560, 9728), (9728, 2560),
                                 (4096, 14336)])
def test_int4_matmul_compiles_or_declines(one_chip, monkeypatch, k, n):
    """K=2560 and K=9728 are the widths Mosaic refused before the
    scale blocks were re-viewed (10 and 38 groups a half); whatever a
    later change does to the block choice, the kernel either compiles
    or declines with None — it never raises at trace time, because
    that would crash `--quantization int4` instead of falling back."""
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    group = 128
    declined = []

    def f(x, q, s):
        y = int4_matmul.int4_matmul(
            x, QTensor(q=q, s=s, bits=4, axis=-2))
        if y is None:
            declined.append(True)
            return x
        return y

    c = _compile(f, one_chip((B, k), jnp.bfloat16),
                 one_chip((k // 2, n), jnp.int8),
                 one_chip((k // group, n), jnp.float32))
    assert declined or "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("k,heads", [(2560, 32), (2560, 8), (4096, 32),
                                     (2048, 32)])
def test_int4_matmul_takes_an_out_major_leaf(one_chip, monkeypatch, k,
                                             heads):
    """The attention projections lie [heads, Dh, D] and an int4 leaf
    of them packs D, its LAST dim (`llama._proj`): Mosaic accepts the
    kernel's out-major form (a row's nibbles along the lanes, both
    operands contracted on their minor dim, the scales a block's
    [channels, groups]) at the hidden sizes of the served models, 10
    groups a nibble half in one k-step at 2560, two k-steps at
    4096."""
    monkeypatch.setattr(device, "on_tpu", lambda: True)

    def f(x, q, s):
        y = int4_matmul.int4_matmul(
            x, QTensor(q=q, s=s, bits=4, axis=-1))
        assert y is not None, "the kernel declined an out-major leaf"
        return y

    c = _compile(f, one_chip((B, k), jnp.bfloat16),
                 one_chip((heads, D, k // 2), jnp.int8),
                 one_chip((heads, D, k // 128), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("merged", [False, True],
                         ids=["heads_apart", "merged_rows"])
@pytest.mark.parametrize("sq,skv", [(1, 2048), (2048, 2048)])
def test_tp4_attention_under_engine_shardings(topo, monkeypatch, sq,
                                              skv, merged):
    """The sharded engine's layout: q on heads, the cache on KV heads
    (its rows merged [B, S, K * D] since PR 38: a chip's heads are
    contiguous lanes, so the merged axis shards as the head axis
    did; heads apart for a caller that holds such rows), over the
    "tp" axis of a (dp, pp, tp) mesh. GSPMD refuses to partition a
    Mosaic kernel, so attention() runs it per device under shard_map —
    and because heads mix nothing, the program holds the kernel and
    NO collective."""
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    import numpy as np
    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4),
                ("dp", "pp", "tp"))
    heads = NamedSharding(mesh, P(None, None, "tp", None))
    rep = NamedSharding(mesh, P())

    def f(q, k, v, positions, kv_len):
        with attn_ops.heads_sharded_over(mesh):
            return attn_ops.attention(q, k, v, positions=positions,
                                      kv_len=kv_len, backend="pallas")

    b = B if sq == 1 else 1       # decode batch, or one prefill
    rows = jax.ShapeDtypeStruct(
        (b, skv, K * D), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, None, "tp"))) if merged \
        else jax.ShapeDtypeStruct((b, skv, K, D), jnp.bfloat16,
                                  sharding=heads)
    c = _compile(
        f, jax.ShapeDtypeStruct((b, sq, H, D), jnp.bfloat16,
                                sharding=heads),
        rows, rows,
        jax.ShapeDtypeStruct((b, sq), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=rep))
    text = c.as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text, collective


def test_int4_quantizer_keeps_no_float32_copy(one_chip):
    """`--quantization int4` quantizes on the device, beside the
    weights it is replacing. One stacked MLP projection of Qwen3-4B is
    1.8 GB in bf16; the quantizer used to hold float32 copies of it
    (3.6 GB each), which does not fit a 16 GB chip that already holds
    the 8 GB model."""
    from ome_tpu.models.quant import quantize_tensor_int4
    w = one_chip((36, 2560, 9728), jnp.bfloat16)
    c = quantize_tensor_int4.lower(w, (1,), group=128).compile()
    assert c.memory_analysis().temp_size_in_bytes < 256 << 20


LAYERS, N_BLOCKS = 36, 198             # the qwen3-4b cells' depth, pool


def _qwen3_4b():
    """Qwen3-4B's ModelConfig at the cells' depth and length."""
    from ome_tpu.models.config import ModelConfig
    return ModelConfig(vocab_size=151936, hidden_size=2560,
                       num_layers=LAYERS, num_heads=H, num_kv_heads=K,
                       head_dim=D, intermediate_size=9728,
                       max_seq_len=2048, qk_norm=True,
                       tie_word_embeddings=True, dtype=jnp.bfloat16)


@pytest.fixture(scope="module")
def decode_paged(topo):
    """`_decode_paged` as engine/core.py builds it (`forward_paged`
    plus `sample` under the `decode` family, the pool donated) at
    Qwen3-4B's widths and depth over the cells' pool, compiled for the
    described chip once per pool dtype: (compiled, pool shape,
    scale-plane shape or None). The whole depth, because it costs
    nothing (the 20 s are the vocabulary-wide sort of `sample`) and
    because under 12 layers the compiler hoists relayout copies of
    the stacked attention weights out of the loop (until PR 41 stored
    them as the dot reads them), which would be read here as the
    pool's."""
    from ome_tpu.engine import core
    from ome_tpu.models import llama
    from ome_tpu.telemetry import scopes

    sharding = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    cfg = _qwen3_4b()

    @scopes.scoped("decode")
    def _decode_paged(params, k, v, ks, vs, lengths, table, tokens, key,
                      temperature, top_k, top_p):
        cache = llama.PagedKVCache(k=k, v=v, index=lengths, table=table,
                                   k_scale=ks, v_scale=vs)
        logits, nc = llama.forward_paged(params, cfg, tokens[:, None],
                                         cache)
        toks = core.sample(logits[:, -1], key, temperature, top_k, top_p)
        return nc.k, nc.v, nc.k_scale, nc.v_scale, toks

    @functools.lru_cache(maxsize=None)
    def compiled(pool_dtype):
        params = jax.tree.map(
            lambda a: struct(a.shape, a.dtype),
            jax.eval_shape(lambda k: llama.init_params(k, cfg),
                           jax.random.PRNGKey(0)))
        quantized = pool_dtype == "int8"
        pool = struct((LAYERS, N_BLOCKS, BS, K, D),
                      jnp.int8 if quantized else jnp.bfloat16)
        scale = (struct((LAYERS, N_BLOCKS, K, BS), jnp.float32)
                 if quantized else None)
        ints, floats = struct((B,), jnp.int32), struct((B,), jnp.float32)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(device, "on_tpu", lambda: True)
            c = jax.jit(_decode_paged,
                        donate_argnums=(1, 2, 3, 4)).lower(
                params, pool, pool, scale, scale, ints,
                struct((B, 16), jnp.int32), ints,
                struct((2,), jnp.uint32), floats, ints,
                floats).compile()
        return c, pool.shape, scale.shape if quantized else None

    return compiled


def test_decode_step_carries_the_programs_names(decode_paged):
    """`forward_paged` plus `sample` at Qwen3-4B widths, under the
    `decode` family as engine/core.py scopes its programs: the
    compiled text names the attention call `paged_attention` and
    carries an `op_name` for every scope the trace reduction reads
    (benchmark/phases.py), through the layer scan's `while`. The
    scopes are metadata: one Mosaic call, as before they existed."""
    text = decode_paged("bf16")[0].as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.search(r"%paged_attention(\.\d+)? = ", text)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(p.startswith("jit(_decode_paged)/decode/") for p in paths)
    for scope in ("decode", "layers", "qkv", "kv_write", "attn", "o_proj",
                  "mlp", "lm_head", "sample"):
        assert any(scope in p.split("/") for p in paths), scope
    # inside the scan the path runs through the loop's body
    assert any("/layers/while/body/" in p and "/mlp/" in p for p in paths)


@pytest.mark.parametrize("pool_dtype", ["bf16", "int8"])
def test_decode_paged_leaves_the_pool_where_it_is(decode_paged,
                                                  pool_dtype):
    """The paged pool is the layer scan's carry, written in place and
    read by the kernel through a layer index: the compiled decode
    step holds one Mosaic call, temporaries under ONE layer's K pool
    (as xs/ys of the scan it kept a second pool: 3.74 GB), and no
    `copy`, `dynamic-slice` or `dynamic-update-slice` whose result is
    the pool or a layer of it, an int8 pool's scale planes
    included."""
    compiled, pool, scale = decode_paged(pool_dtype)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.search(r"%paged_attention(\.\d+)? = ", text)
    one_layer = math.prod(pool[1:]) * (1 if scale else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer

    held = [pool, pool[1:]] + ([scale, scale[1:]] if scale else [])
    shapes = "|".join(",".join(str(d) for d in h) for h in held)
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= \w+\[({shapes})\]\S* (copy|dynamic-slice|"
                          r"dynamic-update-slice)\(", line)]
    assert not moved, moved


@pytest.mark.parametrize("pool_dtype", ["bf16", "int8"])
def test_decode_paged_kernel_takes_the_pool_whole(decode_paged,
                                                  pool_dtype):
    """The kernel walks a slot's chain itself: the step still holds
    ONE Mosaic call, its operands are the lengths, the table, the
    layer index, the queries and the WHOLE pools (an int8 pool's scale
    planes too), left in HBM for the kernel's own copies, and no
    temporary of a layer's pool's size stands beside them."""
    compiled, pool, scale = decode_paged(pool_dtype)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line
             and re.search(r"%paged_attention(\.\d+)? = ", line)]
    assert len(calls) == text.count("tpu_custom_call") == 1
    operands = re.search(r"operand_layout_constraints=\{(.*?)\}\}, ",
                         calls[0]).group(1)
    dims = re.findall(r"\w+\[([\d,]*)\]", operands)
    whole = [",".join(str(d) for d in pool)] * 2
    if scale:
        whole += [",".join(str(d) for d in scale)] * 2
    assert dims == [str(B), f"{B},16", "1", f"{B},{K},{H // K},{D}"] \
        + whole, dims
    one_layer = math.prod(pool[1:]) * (1 if scale else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer


# -- the slab path's decode step over two kinds of KV row ----------------


# the two periodic window / global cells: configuration -> (heads, what
# a decode step's temporaries may be: until PR 41 they read 0.541 GB
# and 0.626 GB (chip compiler, PR 38), nearly all of it the stacks of
# the attention projections re-laid ahead of the layer scan; stored as
# the dot reads them (`llama._proj`) they read 0.015 GB and 0.060 GB)
WINDOW_CELLS = {"trinity-mini-ep4": (32, 30_000_000),
                "smallthinker-21b-a3b-ep4": (28, 80_000_000)}


def _cell_engine(name, struct, mp, layers=None):
    """The engine of a benchmark configuration that runs the slab path
    (benchmark/configs/<name>.json) at its cell's slots and length,
    on shapes: (engine, params, the slots' state, its ModelConfig, the
    cell's prefill buckets). `layers` cuts the depth (a period is
    enough to see what a layer's program holds). `mp` steers the code
    that asks the device."""
    import json

    from ome_tpu.engine.core import InferenceEngine
    from ome_tpu.models import llama
    from ome_tpu.models.config import ModelConfig
    from ome_tpu.perf.ledger import ProgramLedger

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           name + ".json")) as f:
        file = json.load(f)
    if layers:  # and the lists that hold an entry a layer
        full = file["num_hidden_layers"]
        file = {k: v[:layers] if isinstance(v, list) and len(v) == full
                else v for k, v in file.items()}
        file["num_hidden_layers"] = layers
    cfg = ModelConfig.from_hf_config(
        {k: v for k, v in file.items()
         if k not in ("source", "reduced", "assumed", "benchmark")}
    ).replace(moe_impl="ragged")
    serve = file["benchmark"]["serve_args"]
    slots = serve[serve.index("--max-slots") + 1]
    max_seq = serve[serve.index("--max-seq") + 1]
    params = jax.tree.map(struct, jax.eval_shape(
        lambda k: llama.init_params(k, cfg), jax.random.PRNGKey(0)))
    mp.setattr(device, "on_tpu", lambda: True)
    eng = InferenceEngine(params, cfg, max_slots=slots, max_seq=max_seq,
                          ledger=ProgramLedger("off"))
    state = jax.tree.map(struct, jax.eval_shape(eng.new_state))
    return eng, params, state, cfg, file["benchmark"]["prefill_buckets"]


@pytest.fixture(scope="module")
def slab_decode(topo):
    """The engine's own `decode` program for a benchmark configuration
    that runs the slab path, compiled for the described chip once a
    configuration: name -> (compiled, the slots' state as shapes, its
    ModelConfig)."""
    sharding = SingleDeviceSharding(topo.devices[0])

    def struct(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    @functools.lru_cache(maxsize=None)
    def compiled(name):
        with pytest.MonkeyPatch.context() as mp:
            eng, params, state, cfg, _ = _cell_engine(name, struct, mp)
            slots = state.lengths.shape[0]
            ints = struct(jax.ShapeDtypeStruct((slots,), jnp.int32))
            floats = struct(jax.ShapeDtypeStruct((slots,), jnp.float32))
            key = struct(jax.ShapeDtypeStruct((2,), jnp.uint32))
            c = eng.programs["decode"].lower(
                params, state, floats, ints, floats, key).compile()
        return c, state, cfg

    return compiled


def _flash_decode_calls(text):
    """(line, its operands' dims as the kernel constrains them) of
    each `flash_decode` call of a compiled text."""
    out = []
    for line in text.splitlines():
        if "tpu_custom_call" in line \
                and re.search(r"%flash_decode(\.\d+)? = ", line):
            operands = re.search(
                r"operand_layout_constraints=\{(.*?)\}\}, ", line).group(1)
            out.append((line, re.findall(r"\w+\[([\d,]*)\]", operands)))
    return out


def _moved(text, held):
    """The `copy`, `dynamic-slice` and `dynamic-update-slice` lines of
    a compiled text whose result has one of the shapes `held`."""
    shapes = "|".join(",".join(str(d) for d in h) for h in held)
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(rf"= \w+\[({shapes})\]\S* (copy|dynamic-slice|"
                         r"dynamic-update-slice)\(", line)]


def _kv_write_scatters(text):
    """The result shape of each scatter under `kv_write` that updates
    a bf16 array in place."""
    return [re.search(r"= bf16\[([\d,]*)\]", line).group(1)
            for line in text.splitlines()
            if re.search(r"ROOT %scatter\S* = bf16\[", line)
            and "/kv_write/" in line]


@pytest.fixture(scope="module", params=sorted(WINDOW_CELLS))
def decode_window(slab_decode, request):
    """The `decode` program of a configuration of the periodic window
    / global family (trinity-mini-ep4: `long-doc`;
    smallthinker-21b-a3b-ep4: `long-decode`): (compiled, global slab
    shape, ring shape, the configuration's name, its ModelConfig)."""
    compiled, state, cfg = slab_decode(request.param)
    return compiled, state.k.shape, state.wk.shape, request.param, cfg


def test_decode_leaves_both_caches_where_they_are(decode_window):
    """The global layers' slab (long-doc: [4, 24, 16384, 4 * 128],
    long-decode: [6, 8, 16384, 4 * 128]) and the window layers' ring
    ([12, 24, 2048, 512], [18, 8, 4096, 512]) are the layer scan's
    carry, written in place and read by `flash_decode` through a layer
    index in its scalar prefetch: every attention call's operands are
    the WHOLE stacked arrays with a row's K heads merged in the lanes
    (the kernel's key block is a dense [rows, K * D] tile of them),
    the program's temporaries are a fraction of one ring's size (as
    xs/ys of the scan it kept a second slab, 3.2 GB), and no `copy`,
    `dynamic-slice` or
    `dynamic-update-slice` gives a slab, a ring or a layer of
    either."""
    compiled, slab, ring, name, cfg = decode_window
    heads, most_temp = WINDOW_CELLS[name]
    text = compiled.as_text()
    calls = _flash_decode_calls(text)
    # the unrolled layers of the head and the period's 4 in the scan
    P = cfg.sliding_pattern
    head = -(-cfg.first_k_dense // P) * P
    assert len(calls) == head + P
    kinds = {"attn_window": ring, "attn_global": slab}
    B, K, D = slab[1], cfg.num_kv_heads, cfg.head_dim
    assert slab[3:] == ring[3:] == (K * D,)
    for line, dims in calls:
        kind = next(k for k in kinds if f"/{k}/attn/" in line)
        whole = ",".join(str(d) for d in kinds[kind])
        assert whole.endswith(f",{K * D}")
        assert dims == [f"{B},3", f"{B},{K},{heads // K},{D}", whole,
                        whole], dims
    assert sum("/attn_window/" in line for line, _ in calls) \
        == (head + P) * (P - 1) // P
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= most_temp < 0.15 * math.prod(ring) * 2, temp
    moved = _moved(text, [slab, slab[1:], ring, ring[1:]])
    assert not moved, moved


def test_decode_writes_a_steps_rows_in_place(decode_window):
    """A step's fresh rows go into the merged caches by scatters that
    update them in place, two a layer of the program's body (K and V),
    and the weights that made the rows are not re-laid inside the
    layer scan (`llama._rows_as`: without its barrier each layer's
    `wk` / `wv` slice was copied to [hidden, K * D] every step)."""
    compiled, slab, ring, name, cfg = decode_window
    text = compiled.as_text()
    scatters = _kv_write_scatters(text)
    P = cfg.sliding_pattern
    head = -(-cfg.first_k_dense // P) * P
    assert len(scatters) == 2 * (head + P)
    assert set(scatters) <= {",".join(str(d) for d in s)
                             for s in (slab, ring)}, scatters
    K, D, hidden = cfg.num_kv_heads, cfg.head_dim, cfg.hidden_size
    relaid = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= bf16\[{hidden},{K * D}\]\S* "
                           r"(copy|fusion)\(", line)]
    assert not relaid, relaid


def test_decode_names_both_attention_kinds(decode_window):
    """The scopes the benchmark's reduction reads (attn_kinds.py)
    reach the compiled text around `kv_write` and `attn`, inside
    `layers`, and the expert layer's beside them."""
    paths = set(re.findall(r'op_name="([^"]*)"',
                           decode_window[0].as_text()))
    for kind in ("attn_window", "attn_global"):
        for phase in ("kv_write", "attn"):
            assert any(f"/layers/" in p and f"/{kind}/{phase}/" in p
                       for p in paths), (kind, phase)
    shared = decode_window[4].num_shared_experts > 0
    for scope in ("moe_router", "moe_experts") + (("moe_shared",)
                                                  if shared else ()):
        assert any(f"/mlp/{scope}/" in p for p in paths), scope


# what the hybrid cell's decode step may keep in temporaries: it reads
# 0.367 GB (chip compiler, PR 44), none of it rows: the head re-laid
# `bf16[2048,37984]` 0.156, the DeltaNet layers' `w_z` stack
# `copy bf16[9,2048,4096]` 0.151, and the period's weight slices
# (ROADMAP A4's remainder). One slab is 0.403 GB, and a step that
# stacked both anew and sliced a layer out read 1.52 GB
HYBRID_CELL_TEMP = 380_000_000


def test_hybrid_decode_carries_its_slab(slab_decode):
    """The full-attention layers' slabs of `qwen3-next-80b-a3b-ep4`
    (long-batch: [3, 32, 4096, 2 * 256] for K and for V) are the layer
    scan's carry beside the recurrent state (`llama._hybrid_scan`):
    no `copy`, `dynamic-slice` or `dynamic-update-slice` of the
    compiled `decode` gives a slab or a layer of one (as xs / ys of
    the scan it held two of each, 7.4 ms of a 23.4 ms step: ledger,
    PR 41); the period's one `flash_decode` call takes the WHOLE
    stacked arrays and reads its layer through the scalar prefetch;
    a step's rows go in by two scatters in place; and the program's
    temporaries hold no second slab."""
    compiled, state, cfg = slab_decode("qwen3-next-80b-a3b-ep4")
    slab = state.k.shape
    B, K, D = slab[1], cfg.num_kv_heads, cfg.head_dim
    assert slab == state.v.shape
    assert (slab[0], slab[3:]) == (cfg.kv_cache_layers, (K * D,))
    text = compiled.as_text()
    moved = _moved(text, [slab, slab[1:]])
    assert not moved, moved
    whole = ",".join(str(d) for d in slab)
    (line, dims), = _flash_decode_calls(text)
    assert "/layers/while/body/" in line
    assert dims == [f"{B},3", f"{B},{K},{cfg.num_heads // K},{D}", whole,
                    whole], dims
    assert _kv_write_scatters(text) == [whole, whole]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= HYBRID_CELL_TEMP < math.prod(slab) * 2, temp


# -- an expert layer fetches its pairs' results back: no scatter-add --------


def _moe_scatters(text):
    """The result shape of each scatter under `moe_experts`: the
    routed pairs added into their tokens' rows one read-modify-write
    at a time (24 ms a chunk of 4096 tokens at hidden 7680 where the
    rows' bytes take 0.6: ledger, PR 46)."""
    return [re.search(r"= (\w+\[[\d,]*\])", line).group(1)
            for line in text.splitlines()
            if re.search(r"ROOT %scatter\S* = ", line)
            and "/moe_experts/" in line]


def _pair_rows(text, pairs, hidden):
    """The operations of a compiled text (fusion bodies left out)
    whose result is an array of the routed pairs at the hidden size,
    `[T * k, D]`: each is that many bytes written and read again."""
    inner = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    out, body = [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            body = head.group(1)
        elif body not in inner and re.search(
                rf"^\s+(ROOT )?%\S+ = \w+\[{pairs},{hidden}\]\S* "
                r"(?!parameter|get-tuple-element|bitcast)", line):
            out.append(line.strip()[:120])
    return out


def test_prefill_gathers_an_expert_layers_pairs_back(topo):
    """No program adds the routed pairs into `[T, D]` by a scatter:
    `llama.expert_compute` fetches each token's k results by the
    inverse of the dispatch's sort (`Dispatch.place`) and sums them.
    The compiled 8192-bucket prefill of `smallthinker-21b-a3b-ep4` at
    its cell's widths, one period deep (four expert layers, all in
    the scan's body): no scatter under `moe_experts`, and a layer
    keeps three arrays of `[T * k, D]` (the gathered tokens, the
    grouped matmul's result, the results fetched back) where the
    scatter-add kept four (the tokens, `jnp.take`'s fill of them, the
    result, the weighted `contrib`: chip compiler, PR 46)."""
    sharding = SingleDeviceSharding(topo.devices[0])

    def struct(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    def of(shape, dtype):
        return struct(jax.ShapeDtypeStruct(shape, dtype))

    bucket = 8192
    with pytest.MonkeyPatch.context() as mp:
        eng, params, _, cfg, buckets = _cell_engine(
            "smallthinker-21b-a3b-ep4", struct, mp, layers=4)
        assert bucket in buckets
        one_i, one_f = of((1,), jnp.int32), of((1,), jnp.float32)
        text = eng._prefill_fn.lower(
            params, of((1, bucket), jnp.int32), one_i, one_f, one_i,
            one_f, of((2,), jnp.uint32), one_i,
            bucket=bucket).compile().as_text()
    assert "/moe_experts/" in text
    assert _moe_scatters(text) == []
    rows = _pair_rows(text, bucket * cfg.experts_per_token,
                      cfg.hidden_size)
    assert len(rows) == 3 * 4, rows


@pytest.mark.parametrize("name", sorted(WINDOW_CELLS)
                         + ["qwen3-next-80b-a3b-ep4"])
def test_decode_gathers_an_expert_layers_pairs_back(slab_decode, name):
    """The same in a cell's whole `decode`, where T is the slots."""
    text = slab_decode(name)[0].as_text()
    assert "/moe_experts/" in text
    assert _moe_scatters(text) == []


# -- the stacked attention projections lie as the decode dot reads them --


def _relaid_projections(text, cfg, layers):
    """The `copy` / `copy-start` / `copy-done` lines of a compiled
    text that re-lay `wq` / `wk` / `wv` / `w_ogate`: the result is a
    stack or a layer's slice of one in the order it is stored ([L,
    heads, Dh, D]) but with another dimension minor, or has the shape
    of the order they were in ([L, D, heads, Dh]). (A flattened view
    has the shape of other leaves, a shared expert's or a DeltaNet
    mixer's, and is held where it once appeared:
    `test_decode_writes_a_steps_rows_in_place`.) A copy that keeps
    the stored order is the compiler's
    prefetch of a layer's slice into its other memory space (`wo`,
    which lies the same way, always had three a step) and re-lays
    nothing."""
    D, Dh = cfg.hidden_size, cfg.head_dim
    stored, other = set(), set()
    for heads in (cfg.num_heads, cfg.num_kv_heads):
        for lead in ((), (1,), (layers,)):
            stored.add(lead + (heads, Dh, D))
            other.add(lead + (D, heads, Dh))
    out = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\{([\d,]*)\S* "
                      r"copy(-start|-done)?\(", line)
        if not m:
            continue
        shape = tuple(int(d) for d in m.group(1).split(","))
        order = [int(d) for d in m.group(2).split(",")]
        if shape in other or (shape in stored
                              and order != sorted(order, reverse=True)):
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("name", [
    "qwen3-4b", "qwen3-next-80b-a3b-ep4", "trinity-mini-ep4",
    "smallthinker-21b-a3b-ep4"])
def test_decode_relays_no_attention_projection(decode_paged, slab_decode,
                                               name):
    """`wq` / `wk` / `wv` / `w_ogate` are stored [L, heads, Dh, D],
    the order the decode step's dot reads them in (`llama._proj`), so
    the compiled decode program of each of the benchmark's four
    configurations holds no `copy` whose result is a layer's slice of
    one of them (the paged scan's `copy bf16[1,2560,32,128]`, 1.9 ms
    a step) or a whole stack (the window / global and hybrid scans'
    `copy bf16[24,2560,28,128]` ahead of the loop, 1.6 ms): ROADMAP
    A4, ledger PR 39 `breakdown.device_ops`."""
    if name == "qwen3-4b":
        compiled, layers, cfg = decode_paged("bf16")[0], LAYERS, _qwen3_4b()
    else:
        compiled, _, cfg = slab_decode(name)
        # a hybrid model stacks its full-attention layers alone
        layers = (cfg.kv_cache_layers if cfg.is_hybrid
                  else cfg.num_layers - cfg.first_k_dense)
    relaid = _relaid_projections(compiled.as_text(), cfg, layers)
    assert not relaid, relaid


def test_decode_multi_paged_keeps_no_copy_of_the_projections(topo):
    """`decode_multi_paged[n=4]` of the qwen3-4b cells (the engine's
    own program at 16 slots over the pool of 198 blocks): its 1.14 GB
    of temporaries (chip compiler, PR 28-30) were the re-laid stacks
    of `wq` / `wk` / `wv`, hoisted out of the four-step loop. Stored
    as the dot reads them, nothing is re-laid and the temporaries are
    a fraction of one stack."""
    from ome_tpu.engine.core import DecodeState, InferenceEngine
    from ome_tpu.models import llama
    from ome_tpu.perf.ledger import ProgramLedger

    sharding = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    cfg = _qwen3_4b()
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda k: llama.init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    i32, f32 = jnp.int32, jnp.float32
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(device, "on_tpu", lambda: True)
        eng = InferenceEngine(params, cfg, max_slots=B, max_seq=2048,
                              kv_block=BS, kv_blocks=N_BLOCKS,
                              ledger=ProgramLedger("off"))
        pool = S((LAYERS, N_BLOCKS, BS, K, D), cfg.dtype)
        state = DecodeState(k=pool, v=pool, lengths=S((B,), i32),
                            tokens=S((B,), i32), adapters=S((B,), i32))
        compiled = eng.programs["decode_multi_paged"].lower(
            params, state, S((B, eng.max_blocks), i32), S((B,), f32),
            S((B,), i32), S((B,), f32), S((2,), jnp.uint32),
            S((B,), i32), S((B, 4), i32), n=4).compile()
    assert not _relaid_projections(compiled.as_text(), cfg, LAYERS)
    one_stack = LAYERS * H * D * cfg.hidden_size * 2      # wq: 755 MB
    assert compiled.memory_analysis().temp_size_in_bytes < one_stack / 4


def test_flash_decode_reads_a_layer_of_a_stacked_slab(one_chip):
    """The kernel handed stacked slabs and a traced layer index: one
    Mosaic call, no layer sliced out beside it."""
    L, S = 4, 4096
    kv = one_chip((L, B, S, K * D), jnp.bfloat16)

    def f(q, k, v, lo, hi, layer):
        out = flash._flash_decode(q, k, v, lo, hi, D ** -0.5, None,
                                  False, layer=layer)
        assert out is not None
        return out

    c = _compile(f, one_chip((B, 1, H, D), jnp.bfloat16), kv, kv,
                 one_chip((B,), jnp.int32), one_chip((B,), jnp.int32),
                 one_chip((), jnp.int32))
    assert c.as_text().count("tpu_custom_call") == 1
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("layers,rows", [(18, 4096), (6, 16384)])
def test_flash_decode_at_a_group_of_seven(one_chip, layers, rows):
    """smallthinker-21b-a3b-ep4's decode step: 28 query heads on 4 KV
    heads (the first group that is no power of two) over the 18 rings
    of 4096 rows and the 6 global slabs of 16 384, 8 slots: accepted
    by Mosaic as it is, one call, nothing sliced out."""
    slots, heads, kv_heads = 8, 28, 4
    kv = one_chip((layers, slots, rows, kv_heads * D), jnp.bfloat16)

    def f(q, k, v, lo, hi, layer):
        out = flash._flash_decode(q, k, v, lo, hi, D ** -0.5, None,
                                  False, layer=layer)
        assert out is not None, "the kernel declined a group of 7"
        return out

    c = _compile(f, one_chip((slots, 1, heads, D), jnp.bfloat16), kv, kv,
                 one_chip((slots,), jnp.int32),
                 one_chip((slots,), jnp.int32), one_chip((), jnp.int32))
    assert c.as_text().count("tpu_custom_call") == 1
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20


# -- latent attention (MLA): the two kernels of ops/flash.py at
# openpangu-ultra-moe-718b-ep16's widths (128 heads, a cached row of
# 512 + 64 numbers in 640 lanes, 24 slots x 16 384 rows x 5 layers) ---


@pytest.mark.parametrize("lanes,copied", [(640, False), (576, True)])
def test_latent_decode_reads_a_layer_of_a_stacked_slab(one_chip, lanes,
                                                       copied):
    """The kernel handed the stacked latent slab and a traced layer
    index: one Mosaic call and nothing beside it when a row is whole
    lane tiles (640: what `ModelConfig.kv_cache_k_dim` pads 576 to).
    At 576 lanes the chip lays the slab rows-minor and the call takes
    a row-major COPY of it, 2.5 GB a step: the reason for the padding,
    held here so that a compiler that stops doing it is noticed."""
    slots, heads, rank, rope, L, S = 24, 128, 512, 64, 5, 16384
    slab = one_chip((L, slots, S, lanes), jnp.bfloat16)

    def f(q_lat, q_pe, rows, lo, hi, layer):
        out = flash.latent_decode(q_lat, q_pe, rows, lo, hi,
                                  scale=192 ** -0.5, layer=layer)
        assert out is not None
        return out

    c = _compile(f, one_chip((slots, heads, rank), jnp.bfloat16),
                 one_chip((slots, heads, rope), jnp.bfloat16), slab,
                 one_chip((slots,), jnp.int32), one_chip((slots,), jnp.int32),
                 one_chip((), jnp.int32))
    assert c.as_text().count("tpu_custom_call") == 1
    temp = c.memory_analysis().temp_size_in_bytes
    if copied:
        assert temp >= math.prod(slab.shape) * 2, temp
    else:
        assert temp < 1 << 20, temp


@pytest.mark.parametrize("S,heads", [(16384, 32), (8192, 64), (4096, 128)])
def test_latent_prefill_at_the_cells_shapes(one_chip, S, heads):
    """A prompt's materialised heads, a group at a time as
    `mla._prefill_head_group` cuts them at the cell's three buckets:
    query / key width 128 + 64 (two operands), value width 128,
    accepted by Mosaic with four heads a grid step."""
    from ome_tpu.models.mla import _prefill_head_group
    assert _prefill_head_group(S, 128, 128) == heads
    assert flash._latent_prefill_blocks(S, S, heads) == (512, 512, 4)

    def f(q_nope, q_pe, k_nope, k_pe, v, base, kv_hi):
        out = flash.latent_prefill(q_nope, q_pe, k_nope, k_pe, v, base,
                                   kv_hi, scale=192 ** -0.5)
        assert out is not None
        return out

    wide = one_chip((1, heads, S, 128), jnp.bfloat16)
    c = _compile(f, wide, one_chip((1, heads, S, 64), jnp.bfloat16), wide,
                 one_chip((1, S, 64), jnp.bfloat16), wide,
                 one_chip((1,), jnp.int32), one_chip((1,), jnp.int32))
    assert c.as_text().count("tpu_custom_call") == 1
