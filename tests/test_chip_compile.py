"""Real compiles of the main-path Pallas kernels for a described TPU v5e.

Every other kernel test runs the Pallas interpreter, which accepts what
the chip's compiler refuses: a scale-plane BlockSpec that breaks the
(8, 128) rule, a backward launch over the scoped-VMEM limit, an int64
argmax index, a sort inside a kernel. libtpu compiles for a `v5e:2x2`
topology that is described, not attached, so these tests hand each kernel
its shapes at the widths `chip_smoke.py` runs (llama-7b: d 4096, d_ff
11008, 32 heads of 128, vocab 32000) and compile it in this process.
Nothing runs: a pass says the compiler takes the kernel, not that its
results are right — the interpret-mode parity tests say that.

The topology is described inside a fixture, never at import: libtpu
belongs to one process, and under xdist every worker imports this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused_ffn as ff
from paddle_tpu.ops.pallas import fused_sample as fs
from paddle_tpu.ops.pallas import paged_attention as pa

# llama-7b widths (models/llama.py CONFIGS["llama-7b"])
D, D_FF, HEADS, HEAD_DIM, VOCAB, SEQ = 4096, 11008, 32, 128, 32000, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep it off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile `fn` for the described chip; shapes are (shape, dtype), or
    a dict of them."""
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(*sd, sharding=sharding), list(shapes),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _bf16(*shape):
    return shape, jnp.bfloat16


def _flash_loss(q, k, v):
    o = fa._flash_bhtd(q, k, v, np.float32(HEAD_DIM ** -0.5), True, False)
    return jnp.sum(o.astype(jnp.float32))


@pytest.mark.parametrize("q_shape, kv_shape, grad", [
    # the smoke's train step: one sequence of 2048 over 32 heads of 128
    ((1, HEADS, SEQ, HEAD_DIM), (1, HEADS, SEQ, HEAD_DIM), True),
    ((4, 12, SEQ, HEAD_DIM), (4, 12, SEQ, HEAD_DIM), True),
    # the shapes test_mosaic_lowering.py exported: small fwd, fwd+bwd,
    # GQA index maps (h // group on int32), the old bench shape
    ((2, 4, 256, 64), (2, 4, 256, 64), False),
    ((2, 4, 256, 64), (2, 4, 256, 64), True),
    ((2, 8, 256, 64), (2, 2, 256, 64), True),
    ((1, 12, SEQ, HEAD_DIM), (1, 12, SEQ, HEAD_DIM), True),
])
def test_flash_attention_compiles(one_chip, q_shape, kv_shape, grad):
    fn = (jax.value_and_grad(_flash_loss, argnums=(0, 1, 2)) if grad
          else _flash_loss)
    compiled = _compile(fn, one_chip, _bf16(*q_shape), _bf16(*kv_shape),
                        _bf16(*kv_shape))
    # fwd, dq and dkv launches
    assert compiled.as_text().count("tpu_custom_call") >= (3 if grad else 1)


@pytest.mark.parametrize("window", [1024, 700])
def test_windowed_flash_attention_compiles_at_mellum_widths(one_chip, window):
    """The trainer's window layer at the timed shapes: 2 sequences of 8192,
    32 query heads over 4 key-value heads of 128, a window of 1024 (and one
    that is no multiple of a block), forward, dq and dkv; the index maps
    clamp on int32 and the grids are the window's span."""
    def loss(q, k, v):
        o = fa._flash_bhtd_seg(q, k, v, None, None,
                               np.float32(HEAD_DIM ** -0.5), True, False,
                               window)
        return jnp.sum(o.astype(jnp.float32))

    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip,
                        _bf16(2, 32, 8192, HEAD_DIM), _bf16(2, 4, 8192, HEAD_DIM),
                        _bf16(2, 4, 8192, HEAD_DIM))
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("rows, d, d_ff", [
    (SEQ, D, D_FF),          # the smoke's train step, batch 1
    (2 * SEQ, D, D_FF),
    (8192, 1536, 4096),      # the backward Mosaic refused at 18.15M VMEM
])
def test_fused_ffn_fwd_bwd_compiles(one_chip, rows, d, d_ff):
    assert ff.supported(rows, d, d_ff)

    def loss(x, w1, w3, w2):
        o = ff.fused_ffn(x, w1, w3, w2, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
                        one_chip, _bf16(rows, d), _bf16(d, d_ff),
                        _bf16(d, d_ff), _bf16(d_ff, d))
    assert compiled.as_text().count("tpu_custom_call") >= 3   # fwd, dx, dw


@pytest.mark.parametrize("rows", [8, 128, 1024])   # decode tick / chunks
@pytest.mark.parametrize("w8", [False, True])
def test_fused_ffn_serving_compiles(one_chip, rows, w8):
    assert ff.supported(rows, D, D_FF)
    if w8:
        fn = lambda x, q1, s1, q3, s3, q2, s2: ff.fused_ffn_w8(
            x, q1, s1, q3, s3, q2, s2, interpret=False)
        shapes = [_bf16(rows, D),
                  ((D, D_FF), jnp.int8), ((1, D_FF), jnp.float32),
                  ((D, D_FF), jnp.int8), ((1, D_FF), jnp.float32),
                  ((D_FF, D), jnp.int8), ((1, D), jnp.float32)]
    else:
        fn = lambda x, w1, w3, w2: ff.fused_ffn(x, w1, w3, w2,
                                                interpret=False)
        shapes = [_bf16(rows, D), _bf16(D, D_FF), _bf16(D, D_FF),
                  _bf16(D_FF, D)]
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("max_q", [1, 128])        # decode / chunked prefill
@pytest.mark.parametrize("int8_pages", [False, True])
def test_paged_attention_compiles(one_chip, max_q, int8_pages):
    batch, block_size, num_blocks, max_blocks = 8, 16, 576, 72
    assert pa.supported(HEADS, HEADS, HEAD_DIM, block_size)
    page_dtype = jnp.int8 if int8_pages else jnp.bfloat16
    page = ((num_blocks, HEADS, block_size, HEAD_DIM), page_dtype)
    shapes = [_bf16(batch, HEADS, max_q, HEAD_DIM), page, page,
              ((batch, max_blocks), jnp.int32), ((batch,), jnp.int32),
              ((batch,), jnp.int32)]
    if int8_pages:
        shapes += [((num_blocks, HEADS), jnp.float32)] * 2

    def fn(q, k, v, tables, past, this, *dequant):
        return pa.paged_attention(q, k, v, tables, past, this, 1,
                                  HEAD_DIM ** -0.5, *dequant,
                                  interpret=False)

    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("kv, group, num_blocks, max_blocks, rows, int8", [
    # the decode walk (rows == group) at the two serve configurations'
    # shapes: Mistral-7B (8 KV heads, group 4, table 128) and OLMoE-1B-7B
    # (16 KV heads, group 1, table 32), bf16 and int8 pages
    (8, 4, 2304, 128, 4, False), (16, 1, 768, 32, 1, False),
    (8, 4, 2304, 128, 4, True), (16, 1, 768, 32, 1, True),
    # int8 at an 8192-position table: the scale rows come with the pages,
    # so what the launch holds in SMEM is the table alone
    (8, 4, 9216, 512, 4, True),
    # and the BlockSpec walk at those pools (token_budget rows a head: what
    # a mixed launch ran until the mixed walk, below, took head_dim 128)
    (8, 4, 2304, 128, 2048, False), (16, 1, 768, 32, 512, False),
    # SDAR-30B-A3B (4 KV heads, group 8, table 32): the decode walk, which
    # its block rows never take, and the BlockSpec walk
    (4, 8, 768, 32, 8, False), (4, 8, 768, 32, 512, False),
])
def test_paged_attention_on_the_serve_pools_compiles(
        one_chip, kv, group, num_blocks, max_blocks, rows, int8):
    """The kernel alone on the stacked pool with a traced layer: a launch
    whose rows are the GQA group takes the decode walk (whole pages from
    the pool left in HBM, several a key block), any other the BlockSpec
    walk."""
    layers, batch, block_size = 16, 16, 16
    page_dtype = jnp.int8 if int8 else jnp.bfloat16
    pool = ((layers, num_blocks, kv, block_size, HEAD_DIM), page_dtype)
    shapes = [_bf16(batch, kv, rows, HEAD_DIM), pool, pool,
              ((batch, max_blocks), jnp.int32), ((batch,), jnp.int32),
              ((batch,), jnp.int32), ((), jnp.int32)]
    if int8:
        shapes += [((num_blocks, kv), jnp.float32)] * 2

    def fn(q, k, v, tables, past, this, layer, *dequant):
        return pa.paged_attention(q, k, v, tables, past, this, group,
                                  HEAD_DIM ** -0.5, *dequant,
                                  interpret=False, layer=layer)

    text = _compile(fn, one_chip, *shapes).as_text()
    assert ("paged_attention_decode" in text) == (rows == group)
    assert pa.decode_pages_per_block(block_size, kv, HEAD_DIM,
                                     1 if int8 else 2, max_blocks) == 8


@pytest.mark.parametrize(
    "kv, group, num_blocks, max_blocks, small, pages, int8", [
        # the two serve configurations' mixed ticks: Mistral-7B (8 KV heads,
        # group 4, table 128) and OLMoE-1B-7B (16 KV heads, group 1, table
        # 32), token_budget 512, bf16 and int8 pages
        (8, 4, 2304, 128, 8, 32, False), (16, 1, 768, 32, 16, 16, False),
        (8, 4, 2304, 128, 8, 32, True), (16, 1, 768, 32, 16, 32, True),
    ])
def test_mixed_walk_on_the_serve_pools_compiles(
        one_chip, kv, group, num_blocks, max_blocks, small, pages, int8):
    """The mixed walk on the packed stream, inside the stacked pool with a
    traced layer: work items of 64 tokens (x group rows a head) and a
    small tile, key blocks of up to 32 whole pages (512 keys, inside the
    page scratch's budget) from the pool left in HBM."""
    layers, batch, block_size, tokens = 16, 16, 16, 512
    page_dtype = jnp.int8 if int8 else jnp.bfloat16
    pool = ((layers, num_blocks, kv, block_size, HEAD_DIM), page_dtype)
    shapes = [_bf16(tokens, kv, group, HEAD_DIM), pool, pool,
              ((batch, max_blocks), jnp.int32), ((batch,), jnp.int32),
              ((batch,), jnp.int32), ((batch + 1,), jnp.int32),
              ((), jnp.int32)]
    if int8:
        shapes += [((num_blocks, kv), jnp.float32)] * 2

    def fn(q, k, v, tables, past, this, cu, layer, *dequant):
        return pa.paged_attention_packed(q, k, v, tables, past, this, cu,
                                         HEAD_DIM ** -0.5, *dequant,
                                         interpret=False, layer=layer)

    assert pa.whole_pages(HEAD_DIM, interpret=False)
    text = _compile(fn, one_chip, *shapes).as_text()
    assert "paged_attention_mixed" in text
    assert pa.mixed_tiles(tokens, group, kv, HEAD_DIM) == (64, small)
    assert pa.mixed_items(tokens, batch, 64) == 8 + batch
    assert pa.mixed_pages_per_block(block_size, kv, HEAD_DIM,
                                    1 if int8 else 2, max_blocks) == pages


def test_mixed_walk_compiles_under_highest_matmul_precision(one_chip):
    """A reference check runs the engine under
    `jax.default_matmul_precision("highest")` (benchmark/drivers/
    closed_loop_sessions.py), which retraces the tick: Mosaic takes no
    fp32 contract precision on bf16 operands ("Bad lhs type"), so the
    q.k dot names its own."""
    kv, group, num_blocks, max_blocks = 8, 4, 2304, 128
    layers, batch, block_size, tokens = 16, 16, 16, 512
    pool = ((layers, num_blocks, kv, block_size, HEAD_DIM), jnp.bfloat16)

    def fn(q, k, v, tables, past, this, cu, layer):
        return pa.paged_attention_packed(q, k, v, tables, past, this, cu,
                                         HEAD_DIM ** -0.5, interpret=False,
                                         layer=layer)

    with jax.default_matmul_precision("highest"):
        _compile(fn, one_chip, _bf16(tokens, kv, group, HEAD_DIM), pool, pool,
                 ((batch, max_blocks), jnp.int32), ((batch,), jnp.int32),
                 ((batch,), jnp.int32), ((batch + 1,), jnp.int32),
                 ((), jnp.int32))


@pytest.mark.parametrize("head_dim", [16, 64])
@pytest.mark.parametrize("launch", ["decode", "mixed"])
@pytest.mark.parametrize("int8", [False, True])
def test_paged_attention_off_whole_lanes_compiles(one_chip, head_dim, launch,
                                                  int8):
    """Head dims that are no multiple of 128 (`llama-test`'s 16, any
    64-wide model): both launches compile, and the launch of one token a
    sequence takes the BlockSpec walk with max_q = 1 (`pa.whole_pages`)."""
    layers, num_blocks, kv, group, block_size = 2, 64, 2, 2, 8
    batch, max_blocks = 4, 8
    assert pa.supported(kv * group, kv, head_dim, block_size)
    assert not pa.whole_pages(head_dim, interpret=False)
    rows = group if launch == "decode" else 16 * group
    page_dtype = jnp.int8 if int8 else jnp.bfloat16
    pool = ((layers, num_blocks, kv, block_size, head_dim), page_dtype)
    shapes = [_bf16(batch, kv, rows, head_dim), pool, pool,
              ((batch, max_blocks), jnp.int32), ((batch,), jnp.int32),
              ((batch,), jnp.int32), ((), jnp.int32)]
    if int8:
        shapes += [((num_blocks, kv), jnp.float32)] * 2

    def fn(q, k, v, tables, past, this, layer, *dequant):
        return pa.paged_attention(q, k, v, tables, past, this, group,
                                  head_dim ** -0.5, *dequant,
                                  interpret=False, layer=layer)

    text = _compile(fn, one_chip, *shapes).as_text()
    assert "paged_attention" in text
    assert "paged_attention_decode" not in text


def test_decode_walk_is_refused_off_whole_lanes(one_chip):
    """Guards `pa.whole_pages`: the decode walk forced at head_dim 64 is
    what Mosaic refuses. When this stops failing the gate can go."""
    layers, num_blocks, kv, group, block_size, head_dim = 2, 64, 2, 2, 8, 64
    pool = ((layers, num_blocks, kv, block_size, head_dim), jnp.bfloat16)

    def fn(q, k, v, tables, past, this, layer):
        return pa._decode_call(q, k, v, tables, past, this,
                               layer.reshape(1), np.float32(0.125), None,
                               None, False)

    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(fn, one_chip, _bf16(4, kv, group, head_dim), pool, pool,
                 ((4, 8), jnp.int32), ((4,), jnp.int32), ((4,), jnp.int32),
                 ((), jnp.int32))


@pytest.mark.parametrize("hidden, whole_pages", [
    (64, False),      # llama-test as it stands: 4 heads of 16
    (256, True),      # 4 heads of 64: their rows lie in 128 lanes (PR 56)
    (512, True),      # 4 heads of 128: the decode tick takes the decode walk
])
def test_default_engine_ticks_compile(one_chip, monkeypatch, hidden,
                                      whole_pages):
    """`PagedServingEngine(cfg, params)`, told nothing, as a TPU builds it
    (`available` steered true, the program has no option for it): it takes
    the kernel, and its mixed and its decode executable, as the engine
    itself calls them, are compiled for the described chip instead of
    run."""
    import dataclasses

    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L
    cfg = dataclasses.replace(L.CONFIGS["llama-test"], hidden_size=hidden,
                              head_dim=0)       # 0: derived anew
    assert cfg.head_dim == hidden // 4
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    monkeypatch.setattr(fa, "available", lambda: True)
    monkeypatch.setattr(pa, "available", lambda: True)
    eng = PagedServingEngine(cfg, params, block_size=8, max_batch=4,
                             token_budget=32)
    assert eng.pallas is True
    build, texts = eng._build_step, {}

    def compiled_not_run(tok_pad, B, decode=False, *rest):
        fn = build(tok_pad, B, decode, *rest)

        def tick(*args):
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), args)
            texts[decode] = fn.lower(*abstract).compile().as_text()
            return jnp.zeros((B,), jnp.int32), args[1], args[2]
        return tick

    monkeypatch.setattr(eng, "_build_step", compiled_not_run)
    eng.submit([5, 6, 7, 8, 9], max_new_tokens=3)
    eng.run()
    assert set(texts) == {False, True}          # one mixed, one decode
    for text in texts.values():
        assert "paged_cache_write" in text and "paged_attention" in text
    assert "paged_attention_decode" not in texts[False]
    # whole lanes: the whole-page walks, and their counters; else neither
    assert ("paged_attention_mixed" in texts[False]) == whole_pages
    assert ("paged_attention_decode" in texts[True]) == whole_pages
    assert (eng.stats["attn_pages_fetched"] > 0) == whole_pages
    assert (eng.stats["attn_rows_packed"] > 0) == whole_pages


@pytest.mark.parametrize("rows", [16, 512])        # decode / mixed tick
@pytest.mark.parametrize("int8_pages", [False, True])
def test_paged_layer_in_stacked_pool_compiles(one_chip, rows, int8_pages):
    """The serve tick's layer on the stacked pool (Mistral-7B's 8 KV heads
    of 128, 16-token pages): the page-write kernel with the pools aliased
    in to out, then the read through the layer index. No operation of
    the pool's shape but the write kernel's own may come out of it: a
    pool-shaped copy is a layout change of 2.4 GB a layer."""
    import re

    from paddle_tpu.ops.kernels import serving_attention as sa
    # the benchmark's pool: too large for the compiler to stage it anywhere
    layers, num_blocks, kv, block_size = 16, 2304, 8, 16
    batch, max_blocks = 16, 128
    page_dtype = jnp.int8 if int8_pages else jnp.bfloat16
    pool = ((layers, num_blocks, kv, block_size, HEAD_DIM), page_dtype)
    shapes = [_bf16(rows, (HEADS + 2 * kv) * HEAD_DIM), pool, pool,
              ((), jnp.int32), ((batch,), jnp.int32), ((batch,), jnp.int32),
              ((batch + 1,), jnp.int32), ((batch, max_blocks), jnp.int32)]
    if int8_pages:
        shapes += [((kv,), jnp.float32)] * 2
        shapes += [((num_blocks, kv), jnp.float32)] * 2

    def fn(qkv, kp, vp, layer, past, this, cu, tables, *scales):
        return sa.paged_layer_attention(
            qkv, kp, vp, layer, past, this, cu, tables,
            quant_scales=scales or None,
            use_pallas="decode" if rows == batch else True)

    # available() is False here, which would trace the kernels in interpret
    # mode: steer it for this compile (the program has no option for it)
    available = pa.available
    pa.available = lambda: True
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    try:    # the pools are donated, as the tick donates them
        text = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile(
        ).as_text()
    finally:
        pa.available = available
    assert "paged_cache_write" in text
    assert ("paged_attention_decode" if rows == batch
            else "paged_attention_mixed") in text
    shape = re.escape("[%d,%d,%d,%d,%d]" % (layers, num_blocks, kv,
                                            block_size, HEAD_DIM))
    made = re.findall(r"%(\S+) = [^ ]*" + shape + r"\S* (\w[\w-]*)\(", text)
    assert {op for _, op in made} <= {"parameter", "get-tuple-element"}, made


@pytest.mark.parametrize("rows", [16, 512])        # decode / mixed tick
def test_paged_layer_compiles_at_gqa_group_one(one_chip, rows):
    """OLMoE-1B-7B's attention (16 query and 16 KV heads of 128: a GQA
    group of one, so a decode launch's q block is a single row) on its
    benchmark pool."""
    from paddle_tpu.ops.kernels import serving_attention as sa
    layers, num_blocks, heads, block_size = 16, 768, 16, 16
    batch, max_blocks = 16, 32
    assert pa.supported(heads, heads, HEAD_DIM, block_size)
    pool = ((layers, num_blocks, heads, block_size, HEAD_DIM), jnp.bfloat16)
    shapes = [_bf16(rows, 3 * heads * HEAD_DIM), pool, pool,
              ((), jnp.int32), ((batch,), jnp.int32), ((batch,), jnp.int32),
              ((batch + 1,), jnp.int32), ((batch, max_blocks), jnp.int32)]

    def fn(qkv, kp, vp, layer, past, this, cu, tables):
        return sa.paged_layer_attention(
            qkv, kp, vp, layer, past, this, cu, tables,
            use_pallas="decode" if rows == batch else True)

    available = pa.available
    pa.available = lambda: True
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    try:
        text = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile(
        ).as_text()
    finally:
        pa.available = available
    assert "paged_cache_write" in text and "paged_attention" in text


@pytest.mark.parametrize("rows", [16, 512])        # decode / mixed tick
def test_routed_ffn_on_stacked_experts_compiles_without_a_copy(one_chip,
                                                               rows):
    """OLMoE-1B-7B's routed FFN as the serve tick calls it: the stacked
    expert leaves and a layer index, under this package's jax_enable_x64
    (which the grouped-matmul kernel cannot be traced with, so
    `_grouped_matmul` turns it off around the call). No operation may
    make anything of one layer's expert matrix's size: a slice of the
    stack handed to the kernel is a copy of 268 MB, three a layer."""
    import re

    from paddle_tpu.models import llama as L
    cfg = L.LlamaConfig(vocab_size=50304, hidden_size=2048,
                        intermediate_size=1024, num_layers=16, num_heads=16,
                        num_kv_heads=16, num_experts=64, top_k=8,
                        qk_norm=True, norm_topk_prob=False,
                        param_dtype=jnp.bfloat16)
    lp = {"router": _bf16(2048, 64), "w1": _bf16(16, 64, 2048, 1024),
          "w3": _bf16(16, 64, 2048, 1024), "w2": _bf16(16, 64, 1024, 2048)}

    def fn(h, valid, layer, router, w1, w3, w2):
        return L.routed_ffn_load(
            h, {"router": router, "w1": w1, "w3": w3, "w2": w2}, cfg,
            valid, layer=layer)

    available = fa.available
    fa.available = lambda: True         # expert_form and interpret read it
    try:
        assert L.expert_form(cfg) == "sorted_gmm"
        text = _compile(fn, one_chip, _bf16(rows, 2048),
                        ((rows,), jnp.bool_), ((), jnp.int32),
                        lp["router"], lp["w1"], lp["w3"], lp["w2"]).as_text()
    finally:
        fa.available = available
    made = re.findall(r"%(\S+) = bf16\[(?:64|1024),(?:2048|1024),"
                      r"(?:1024|2048)\]\S* (\w[\w-]*)\(", text)
    assert {op for _, op in made} <= {"parameter", "bitcast"}, made


@pytest.mark.parametrize("k, n", [(2304, 896), (896, 2304)])
def test_grouped_matmul_trains_at_mellum_widths(one_chip, k, n):
    """The sorted expert form's grouped product forward and backward at
    Mellum2-12B-A2.5B's expert (2304 x 896: w1 / w3 one way, w2 the
    other), 16 held experts. The weights' gradient is megablox's `tgmm`
    under `_grouped_matmul`'s own tiling: under the forward's (an
    expert's sides are no multiple of 1024, so whole) its accumulator and
    output tile pass the chip's 16 MB of scoped VMEM (17.31 MB; PR 47 read
    the refusal here before any chip call)."""
    from paddle_tpu.models import llama as L

    def loss(xs, w, sizes):
        y = L._grouped_matmul(xs, w, sizes, jnp.zeros((), jnp.int32))
        return jnp.sum(y.astype(jnp.float32))

    available = fa.available
    fa.available = lambda: True         # `interpret` reads it
    try:
        text = _compile(jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
                        _bf16(2048, k), _bf16(16, k, n),
                        ((16,), jnp.int32)).as_text()
    finally:
        fa.available = available
    assert text.count("tpu_custom_call") >= 3     # gmm, gmm transposed, tgmm
    assert L._fit(2304, 1024) == 768 and L._fit(896, 1024) == 896
    assert L._fit(2304, 1024 * 1024 // 896) == 1152


def _computations(text):
    """{name: body} of every computation of an HLO module's text, and for
    each the names of the computations it calls."""
    import re
    bodies = {}
    for block in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text):
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", block)
        if m:
            bodies[m.group(1)] = block
    calls = {n: set(re.findall(r"%([\w.\-]+)", " ".join(re.findall(
        r"(?:calls|to_apply|body|condition|branch_computations|"
        r"true_computation|false_computation)=\{?([^}\n]*?)[},\n]", b))))
        & set(bodies) for n, b in bodies.items()}
    return bodies, calls


def _reached(calls, start):
    seen, todo = set(), [start]
    while todo:
        n = todo.pop()
        if n not in seen:
            seen.add(n)
            todo.extend(calls[n])
    return seen


def test_a_quarter_share_trains_its_compact_form_at_mellum_widths(one_chip):
    """One sparse layer of `mellum2-12b-a2.5b-train` (16 of 64 experts
    held, 8 a row), value and gradient at the cell's launch of 16,384 rows:
    65,536 places of 131,072, so one `conditional` forward and one backward
    whose compact branches run the grouped products on 65,536 rows and
    whose other branches run them on all 131,072 (the fallback past the
    places: nothing dropped); no float32 copy of 131,072 rows is made
    outside that fallback (the way back gathers a row's k places in bf16
    and sums them inside one fusion), no row is added into place one at a
    time in the compact branches (the transposes are gathers), and the
    compiler's peak
    for the layer is under the 3.60 GB it read for the parent's whole form
    (PR 48: 3.48; both branches recompute under `jax.checkpoint`, so what
    crosses a `conditional` is the layer's input and output alone)."""
    import re

    from paddle_tpu.models import llama as L
    rows, d, f = 16384, 2304, 896
    cfg = L.LlamaConfig(hidden_size=d, intermediate_size=f, num_experts=64,
                        top_k=8, experts_held=(0, 16), norm_topk_prob=True,
                        dtype=jnp.bfloat16)
    assert L.held_pair_slots(rows, cfg) == 65536
    assert not L.combine_is_a_product(65536, rows, 8)

    def loss(h, lp):
        return jnp.sum(L.routed_ffn_load(h, lp, cfg)[0].astype(
            jnp.float32) ** 2)

    available = fa.available
    fa.available = lambda: True         # expert_form and interpret read it
    try:
        compiled = _compile(
            jax.value_and_grad(loss, argnums=(0, 1)), one_chip, _bf16(rows, d),
            {"router": ((d, 64), jnp.float32), "w1": ((16, d, f), jnp.float32),
             "w3": ((16, d, f), jnp.float32), "w2": ((16, f, d), jnp.float32)})
    finally:
        fa.available = available
    text = compiled.as_text()
    bodies, calls = _computations(text)
    conds = re.findall(r" conditional\(.*?branch_computations=\{([^}]*)\}"
                       r"|true_computation=%([\w.\-]+), "
                       r"false_computation=%([\w.\-]+)", text)
    assert len(conds) == 2, conds                   # forward, backward
    fallback = set()
    for listed, true, false in conds:
        branches = re.findall(r"%([\w.\-]+)", listed) or [true, false]
        by_rows = {}
        for b in branches:
            inside = "".join(bodies[c] for c in _reached(calls, b))
            made = set(re.findall(r"bf16\[(\d+),896\]\S* custom-call\(",
                                  inside))
            assert len(made) == 1, (b, made)
            by_rows[made.pop()] = b
        assert set(by_rows) == {"65536", "131072"}, by_rows
        fallback |= _reached(calls, by_rows["131072"])
        compact = "".join(bodies[c] for c in _reached(calls, by_rows["65536"]))
        wide = re.findall(r"= \w+\[\d+,2304\]\S* scatter\(", compact)
        assert not wide, wide
    outside = [n for n, b in bodies.items()
               if n not in fallback and "f32[131072,2304]" in b]
    assert not outside, outside
    peak = getattr(compiled.memory_analysis(), "peak_memory_in_bytes", None)
    assert peak is None or peak < 3.55e9, peak


@pytest.mark.parametrize("microbatches", [1, 2], ids=["one_slot", "two_slots"])
def test_the_train_step_runs_a_layers_forward_pass_twice_not_three_times(
        one_chip, monkeypatch, microbatches):
    """`train_1chip`'s whole step (mistral7b-train-1chip: one layer, batch 4
    x 4096, mesh 1·1·1, full remat) for the described chip: the layer's
    flash forward kernel is launched from two sites, the forward pass and
    the remat replay beside the one `dq` and the one `dkv`. As a scan of
    length one the one-slot schedule compiled to three (PR 49: the body is
    loop-invariant, the compiler cannot see the trip count, and it lifted a
    second copy of the layers and the head out of the loop while the copy
    that feeds the residuals stayed inside; 11.66 GiB, now 9.41). Two
    microbatches keep the scan, and its two sites."""
    import json
    import os
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.lib.program import DTYPES, llama_config
    from paddle_tpu.distributed import hybrid as H
    from paddle_tpu.models import llama as L
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mistral7b-train-1chip.json")) as f:
        cfg = json.load(f)
    lcfg = llama_config(cfg, DTYPES[cfg["trainer"]["param_dtype"]])
    assert lcfg.num_layers == 1
    monkeypatch.setattr(fa, "available", lambda: True)
    mesh = H.build_mesh(1, 1, 1, devices=list(one_chip.device_set))
    specs = H.param_specs(lcfg)

    def placed(shapes, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            shapes, specs)

    shapes = jax.eval_shape(
        lambda k: H.stack_pipeline(L.init_params(lcfg, k), 1),
        jax.random.PRNGKey(0))
    opt = placed(jax.eval_shape(H.init_opt_state, shapes),
                 {"m": specs, "v": specs, "step": P()})
    tokens = jax.ShapeDtypeStruct((4, 4096), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp", None)))
    compiled = H.make_train_step(lcfg, mesh, num_microbatches=microbatches
                                 ).lower(placed(shapes, specs), opt, tokens,
                                         tokens).compile()
    sites = re.findall(r"%flash_attention_(fwd|dq|dkv)[\w.]* = [^\n]*? "
                       r"custom-call\(", compiled.as_text())
    assert sorted(sites) == ["dkv", "dq", "fwd", "fwd"], sites
    peak = getattr(compiled.memory_analysis(), "peak_memory_in_bytes", None)
    if microbatches == 1:
        assert peak is None or peak < 10 * 2 ** 30, peak


# SDAR-30B-A3B-Chat (benchmark/configs/sdar30b-a3b-serve.json): hidden 2048,
# 32 q / 4 kv heads of 128, 128 experts of 768, vocabulary 151,936
SDAR = dict(vocab_size=151936, hidden_size=2048, intermediate_size=768,
            num_heads=32, num_kv_heads=4, head_dim=128, max_seq_len=512,
            rope_theta=1e6, rms_eps=1e-6, num_experts=128, top_k=8,
            qk_norm=True, qk_norm_per_head=True, norm_topk_prob=True,
            block_length=4, mask_token_id=151669,
            param_dtype=jnp.bfloat16)


@pytest.mark.parametrize("tokens, block_len", [
    (64, 4), (512, 4),      # a tick of 16 blocks, a tick with a prefill chunk
    (64, 0),                # the same shapes under the causal mask
])
def test_block_causal_mixed_walk_compiles(one_chip, tokens, block_len):
    """The mixed walk at SDAR's shapes (4 KV heads, GQA group 8, table of
    32 pages) under the block-causal mask, inside the depth-7 pool."""
    kv, group, layers, batch, block_size = 4, 8, 7, 16, 16
    pool = ((layers, 768, kv, block_size, HEAD_DIM), jnp.bfloat16)

    def fn(q, k, v, tables, past, this, cu, layer):
        return pa.paged_attention_packed(q, k, v, tables, past, this, cu,
                                         HEAD_DIM ** -0.5, interpret=False,
                                         layer=layer, block_len=block_len)

    text = _compile(fn, one_chip, _bf16(tokens, kv, group, HEAD_DIM), pool,
                    pool, ((batch, 32), jnp.int32), ((batch,), jnp.int32),
                    ((batch,), jnp.int32), ((batch + 1,), jnp.int32),
                    ((), jnp.int32)).as_text()
    assert "paged_attention_mixed" in text
    # 64 tokens a work item, 8 (64 rows a KV head) on the small tile that a
    # block of 4 takes; a key block is the whole table of 32 pages
    assert pa.mixed_tiles(tokens, group, kv, HEAD_DIM) == (64, 8)
    assert pa.mixed_pages_per_block(block_size, kv, HEAD_DIM, 2, 32) == 32


@pytest.mark.parametrize("rows", [64, 512])     # a block tick / a mixed tick
def test_routed_ffn_at_sdar_widths_compiles(one_chip, rows):
    """SDAR's routed FFN as the serve tick calls it: 128 experts of 768,
    8 a row renormalised, the stacked leaves of depth 7 and a layer
    index."""
    from paddle_tpu.models import llama as L
    cfg = L.LlamaConfig(num_layers=7, **SDAR)

    def fn(h, valid, layer, router, w1, w3, w2):
        return L.routed_ffn_load(
            h, {"router": router, "w1": w1, "w3": w3, "w2": w2}, cfg,
            valid, layer=layer)

    available = fa.available
    fa.available = lambda: True         # expert_form and interpret read it
    try:
        assert L.expert_form(cfg) == "sorted_gmm"
        _compile(fn, one_chip, _bf16(rows, 2048), ((rows,), jnp.bool_),
                 ((), jnp.int32), _bf16(2048, 128), _bf16(7, 128, 2048, 768),
                 _bf16(7, 128, 2048, 768), _bf16(7, 128, 768, 2048))
    finally:
        fa.available = available


def test_sdar_depth7_ticks_fit_the_chip(one_chip, monkeypatch):
    """The cell `serve_blockdiff_decode` as the engine builds it on a TPU
    (`available` steered true), at the published widths and depth 7: both
    executables (a tick with a prefill chunk, token_budget 512 rows; a
    tick of blocks alone, 16 x 4 rows) compile for the described v5e and
    the compiler counts each under the chip's 15.75 GiB."""
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L
    cfg = L.LlamaConfig(num_layers=7, **SDAR)
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    monkeypatch.setattr(fa, "available", lambda: True)
    monkeypatch.setattr(pa, "available", lambda: True)
    eng = PagedServingEngine(cfg, params, num_blocks=768, block_size=16,
                             max_batch=16, token_budget=512, max_len=512,
                             pallas=True, pallas_ffn=False)
    build, gib = eng._build_step, {}

    def compiled_not_run(tok_pad, B, *rest):
        fn = build(tok_pad, B, *rest)

        def tick(*args):
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), args)
            compiled = fn.lower(*abstract).compile()
            assert "paged_attention_mixed" in compiled.as_text()
            m = compiled.memory_analysis()
            gib[tok_pad] = (m.argument_size_in_bytes + m.output_size_in_bytes
                            + m.temp_size_in_bytes
                            - m.alias_size_in_bytes) / 2 ** 30
            return (jnp.zeros((B * cfg.block_length * 3 + 3,), jnp.int32),
                    args[1], args[2])
        return tick

    monkeypatch.setattr(eng, "_build_step", compiled_not_run)
    eng.submit(list(range(1, 70)), max_new_tokens=8)
    eng.step()                  # 68 positions of prefill
    eng.step()                  # the open block alone
    assert set(gib) == {512, 64}
    assert all(g < 15.75 for g in gib.values()), gib
    print("SDAR depth-7 GiB by tok_pad:", gib)


@pytest.mark.parametrize("group, window, launch", [
    (8, 512, "decode"), (8, 512, "mixed"),     # Laguna's window layers
    (6, 0, "decode"), (6, 0, "mixed"),         # its full layers: 48 / 8
])
def test_laguna_walks_compile(one_chip, group, window, launch):
    """Both whole-page walks at Laguna-XS.2's shapes (8 key-value heads of
    128, 6 and 8 query rows a head, tables of 576 entries, 32 slots, the
    window pool's 4096 pages of 3 layers and the full pool's 20480 of 2)
    under the window and without: the lower limit's arithmetic and row
    counts off the 16-row tile lower for the chip."""
    layers, blocks = (3, 4096) if window else (2, 20480)
    batch, kv, tokens = 32, 8, 512
    pool = _bf16(layers, blocks, kv, 16, HEAD_DIM)
    lens = [((batch, 576), jnp.int32), ((batch,), jnp.int32),
            ((batch,), jnp.int32)]
    if launch == "decode":
        def fn(q, k, v, tables, past, this, layer):
            return pa.paged_attention(q, k, v, tables, past, this, group,
                                      HEAD_DIM ** -0.5, interpret=False,
                                      layer=layer, window=window)
        shapes = [_bf16(batch, kv, group, HEAD_DIM), pool, pool, *lens,
                  ((), jnp.int32)]
    else:
        def fn(q, k, v, tables, past, this, cu, layer):
            return pa.paged_attention_packed(
                q, k, v, tables, past, this, cu, HEAD_DIM ** -0.5,
                interpret=False, layer=layer, window=window)
        shapes = [_bf16(tokens, kv, group, HEAD_DIM), pool, pool, *lens,
                  ((batch + 1,), jnp.int32), ((), jnp.int32)]
    text = _compile(fn, one_chip, *shapes).as_text()
    assert f"paged_attention_{launch}" in text


def test_laguna_depth5_ticks_fit_the_chip(one_chip, monkeypatch):
    """The cell `serve_window_longctx_decode` as the engine builds it on a
    TPU (`available` steered true), from the configuration file itself:
    both executables (a tick with a prefill chunk, 512 rows; a decode tick,
    32 rows) compile for the described v5e with both pools in their carry,
    and the compiler counts each over 25 % and under the chip's 15.75 GiB
    (10.56 and 10.49 GiB, PR 34)."""
    import json
    import os

    from benchmark.drivers import closed_loop_serve_longctx as D
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-xs2-serve.json")) as f:
        file = json.load(f)
    cfg, e = D.laguna_config(file, jnp.bfloat16), file["engine"]
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    monkeypatch.setattr(fa, "available", lambda: True)
    monkeypatch.setattr(pa, "available", lambda: True)
    eng = PagedServingEngine(
        cfg, params, num_blocks=e["num_blocks"], block_size=e["block_size"],
        max_batch=e["max_batch"], token_budget=e["token_budget"],
        max_len=e["max_len"], pallas=True, pallas_ffn=False)
    assert eng.window_blocks == e["window_blocks"]      # through the flag
    build, gib = eng._build_step, {}

    def compiled_not_run(tok_pad, B, *rest):
        fn = build(tok_pad, B, *rest)

        def tick(*args):
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), args)
            compiled = fn.lower(*abstract).compile()
            text = compiled.as_text()
            assert ("paged_attention_mixed" in text) == (tok_pad == 512)
            assert ("paged_attention_decode" in text) == (tok_pad == 32)
            m = compiled.memory_analysis()
            gib[tok_pad] = (m.argument_size_in_bytes + m.output_size_in_bytes
                            + m.temp_size_in_bytes
                            - m.alias_size_in_bytes) / 2 ** 30
            return jnp.zeros((B + 3,), jnp.int32), args[1], args[2]
        return tick

    monkeypatch.setattr(eng, "_build_step", compiled_not_run)
    eng.submit(list(range(1, 70)), max_new_tokens=4)
    eng.step()                  # the prompt, one chunk
    eng.step()                  # a decode row
    assert set(gib) == {512, 32}
    assert all(0.25 * 15.75 < g < 15.75 for g in gib.values()), gib
    print("Laguna depth-5 GiB by tok_pad:", gib)


@pytest.mark.parametrize("launch", ["decode", "mixed", "write"])
def test_latent_walks_compile(one_chip, launch):
    """Both latent walks and the page write at Kimi-K2.6's shapes (one
    latent pool of 6 layers x 38,912 pages of 16 rows, a row of 576 values
    in 640 lanes, group 64: all heads on one key row, 64 slots, tables of
    576 entries, a 1,024-token stream) lower for the chip."""
    from paddle_tpu.ops.pallas import paged_attention_latent as pl_
    batch, heads, tokens = 64, 64, 1024
    width = pl_.padded_width(576)
    assert width == 640
    pool = _bf16(6, 38912, 1, 16, width)
    lens = [((batch, 576), jnp.int32), ((batch,), jnp.int32),
            ((batch,), jnp.int32)]
    if launch == "decode":
        def fn(q, pool, tables, past, this, layer):
            return pl_.latent_attention(q, pool, tables, past, this, 0.1447,
                                        layer, 512, interpret=False)
        shapes = [_bf16(batch, heads, width), pool, *lens, ((), jnp.int32)]
    elif launch == "mixed":
        def fn(q, pool, tables, past, this, cu, layer):
            return pl_.latent_attention_packed(
                q, pool, tables, past, this, cu, 0.1447, layer, 512,
                interpret=False)
        shapes = [_bf16(tokens, heads, width), pool, *lens,
                  ((batch + 1,), jnp.int32), ((), jnp.int32)]
    else:
        n = tokens // 16 + 2 * batch

        def fn(pool, layer, pages, lo, hi, new):
            return pl_.write_latent_pages(pool, layer, pages, lo, hi, new,
                                          interpret=False)
        shapes = [pool, ((), jnp.int32), *[((n,), jnp.int32)] * 3,
                  _bf16(n, 1, 16, width)]
    text = _compile(fn, one_chip, *shapes).as_text()
    assert ("paged_cache_write_latent" if launch == "write"
            else f"paged_attention_latent_{launch}") in text


def test_latent_walk_is_refused_at_a_row_off_whole_lanes(one_chip):
    """Why the pool's rows are 640 lanes and not 576: Mosaic takes no
    whole-page copy out of a pool whose rows are 4.5 lane tiles wide."""
    from paddle_tpu.ops.pallas import paged_attention_latent as pl_

    def fn(q, pool, tables, past, this, layer):
        return pl_.latent_attention(q, pool, tables, past, this, 0.1447,
                                    layer, 512, interpret=False)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(fn, one_chip, _bf16(8, 64, 576), _bf16(2, 64, 1, 16, 576),
                 ((8, 16), jnp.int32), ((8,), jnp.int32), ((8,), jnp.int32),
                 ((), jnp.int32))


def _kimi_file():
    """`benchmark/configs/kimi-k2.6-serve.json`, as the cell reads it."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-k2.6-serve.json")) as f:
        return json.load(f)


def test_kimi_depth6_ticks_fit_the_chip(one_chip, monkeypatch):
    """The cell `serve_latent_longctx_decode` as the engine builds it on a
    TPU (`available` steered true), from the configuration file itself:
    both executables (a tick with a prefill chunk, 1,024 rows; a decode
    tick, 64 rows) compile for the described v5e with the one latent pool
    in their carry and 12 of 384 experts held, and the compiler counts each
    over 25 % and under the chip's 15.75 GiB, and no higher than before the
    held experts' pairs got their compact form."""
    from benchmark.drivers import closed_loop_serve_latent as D
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L
    file = _kimi_file()
    cfg, e = D.kimi_config(file, jnp.bfloat16), file["engine"]
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert params["blocks"][1]["w1"].shape == (5, 12, 7168, 2048)
    assert params["blocks"][1]["router"].shape == (5, 7168, 384)
    from paddle_tpu.ops.pallas import paged_attention_latent as pl_
    for module in (fa, pa, pl_):
        monkeypatch.setattr(module, "available", lambda: True)
    eng = PagedServingEngine(
        cfg, params, num_blocks=e["num_blocks"], block_size=e["block_size"],
        max_batch=e["max_batch"], token_budget=e["token_budget"],
        max_len=e["max_len"], pallas=True, pallas_ffn=False)
    assert eng._value_cache is None
    assert eng._key_cache.shape == (6, 38912, 1, 16, 640)
    build, gib = eng._build_step, {}

    def compiled_not_run(tok_pad, B, *rest):
        fn = build(tok_pad, B, *rest)

        def tick(*args):
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), args)
            compiled = fn.lower(*abstract).compile()
            text = compiled.as_text()
            # a tick with a chunk runs both: its one-row sequences go
            # through the decode launch
            assert ("paged_attention_latent_mixed" in text) == (
                tok_pad == 1024)
            assert "paged_attention_latent_decode" in text
            m = compiled.memory_analysis()
            gib[tok_pad] = (m.argument_size_in_bytes + m.output_size_in_bytes
                            + m.temp_size_in_bytes
                            - m.alias_size_in_bytes) / 2 ** 30
            return (jnp.zeros((B + len(eng._moe_fields),), jnp.int32),
                    args[1], args[2])
        return tick

    monkeypatch.setattr(eng, "_build_step", compiled_not_run)
    eng.submit(list(range(1, 70)), max_new_tokens=4)
    eng.step()                  # the prompt, one chunk
    eng.step()                  # a decode row
    assert set(gib) == {1024, 64}
    assert all(0.25 * 15.75 < g < 15.75 for g in gib.values()), gib
    # PR 41's readings, 12.79 and 12.27: the compact form of the held
    # experts (PR 42) and the whole form behind one `cond` share their
    # buffers (12.7878 -> 12.7884, 12.2705 -> 12.2711)
    assert gib[1024] < 12.795 and gib[64] < 12.275, gib
    print("Kimi depth-6 GiB by tok_pad:", gib)


def test_xing_depth6_ticks_fit_the_chip(one_chip, monkeypatch):
    """The cell `serve_hyper_latent_mixed_4k` as the engine builds it on a
    TPU (`available` steered true), from the configuration file itself:
    both executables (a tick with a prefill chunk, 2,048 rows of a stream
    of four lanes; a decode tick, 64 rows) compile for the described v5e
    with the latent walks at 32 heads, every one of the 64 experts held and
    the lanes' mixing in every layer, and the compiler counts each over
    25 % and under the chip's 15.75 GiB with room for the float32 check."""
    import json
    import os

    from benchmark.drivers import closed_loop_serve_hyper as D
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "xing4.0-29b-a4b-serve.json")) as f:
        file = json.load(f)
    cfg, e = D.xing_config(file, jnp.bfloat16), file["engine"]
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert params["blocks"][1]["w1"].shape == (5, 64, 3584, 1024)
    assert params["blocks"][1]["hc_mlp_phi"].shape == (5, 24, 14336)
    from paddle_tpu.ops.pallas import paged_attention_latent as pl_
    for module in (fa, pa, pl_):
        monkeypatch.setattr(module, "available", lambda: True)
    eng = PagedServingEngine(
        cfg, params, num_blocks=e["num_blocks"], block_size=e["block_size"],
        max_batch=e["max_batch"], token_budget=e["token_budget"],
        max_len=e["max_len"], pallas=True, pallas_ffn=False)
    assert eng._value_cache is None
    assert eng._key_cache.shape == (6, 27648, 1, 16, 640)
    build, gib = eng._build_step, {}

    def compiled_not_run(tok_pad, B, *rest):
        fn = build(tok_pad, B, *rest)

        def tick(*args):
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), args)
            compiled = fn.lower(*abstract).compile()
            text = compiled.as_text()
            assert ("paged_attention_latent_mixed" in text) == (
                tok_pad == 2048)
            assert "paged_attention_latent_decode" in text
            assert "hyper_post" in text and "hyper_coeff" in text
            m = compiled.memory_analysis()
            gib[tok_pad] = (m.argument_size_in_bytes + m.output_size_in_bytes
                            + m.temp_size_in_bytes
                            - m.alias_size_in_bytes) / 2 ** 30
            return (jnp.zeros((B + len(eng._moe_fields),), jnp.int32),
                    args[1], args[2])
        return tick

    monkeypatch.setattr(eng, "_build_step", compiled_not_run)
    eng.submit(list(range(1, 70)), max_new_tokens=4)
    eng.step()                  # the prompt, one chunk
    eng.step()                  # a decode row
    assert set(gib) == {2048, 64}
    assert all(0.25 * 15.75 < g < 15.75 for g in gib.values()), gib
    print("Xing depth-6 GiB by tok_pad:", gib)


@pytest.mark.parametrize("launch", [
    "decode_window", "mixed_window", "decode_full", "mixed_full",
    "masked_full", "index", "index_decode", "write_index"])
def test_sparse_latent_walks_compile(one_chip, launch):
    """What dots3-note-prev's two kinds of latent layer launch, at the
    cell's shapes (32 slots, tables of 2,080 entries, a 2,048-token
    stream): the windowed walks of the sliding layers (a pool of 3 layers x
    6,144 pages of 16 rows, a row of 1,088 values in 1,152 lanes, 64 heads,
    a window of 513), the dense walks of the full layers at 128 heads (2
    layers x 68,608 pages, 640 lanes; a work item's row tile halves to 16
    tokens; and the same walk under a selection's bits [2,048, 260, 4], the
    masked walk of a selecting chunk, PR 44), the index walk of a chunk (64
    index heads of 128 against each sequence's 33,280 index keys, copied a
    key tile's 32 pages at a time out of the stacked index pool of 2 layers
    x 68,608 pages, PR 45), its one-row form at 32 sequences and the page
    write of the index keys (a row of 128 lanes) lower for the chip."""
    from paddle_tpu.ops.pallas import paged_attention_latent as pl_
    batch, tokens, entries = 32, 2048, 2080
    lens = [((batch, entries), jnp.int32), ((batch,), jnp.int32),
            ((batch,), jnp.int32)]
    kind, _, pool_kind = launch.partition("_")
    heads, pool, vdim, kw = (
        (64, _bf16(3, 6144, 1, 16, pl_.padded_width(1088)), 1024,
         dict(window=513)) if pool_kind == "window"
        else (128, _bf16(2, 68608, 1, 16, pl_.padded_width(576)), 512, {}))
    width = pool[0][-1]
    if kind == "decode":
        def fn(q, pool, tables, past, this, layer):
            return pl_.latent_attention(q, pool, tables, past, this, 0.0625,
                                        layer, vdim, interpret=False, **kw)
        shapes = [_bf16(batch, heads, width), pool, *lens, ((), jnp.int32)]
        name = "paged_attention_latent_decode"
    elif kind in ("mixed", "masked"):
        assert pl_.mixed_tokens(tokens, heads) * heads == 2048
        def fn(q, pool, tables, past, this, cu, layer, *mask):
            return pl_.latent_attention_packed(
                q, pool, tables, past, this, cu, 0.0625, layer, vdim,
                interpret=False, **kw, **dict(zip(("mask",), mask)))
        shapes = [_bf16(tokens, heads, width), pool, *lens,
                  ((batch + 1,), jnp.int32), ((), jnp.int32)]
        if kind == "masked":
            shapes.append(((tokens, entries * 16 // 128, 4), jnp.uint32))
        name = "paged_attention_latent_" + kind
    elif launch == "index":
        def fn(qi, w, pool, tables, past, this, cu, layer):
            return pl_.index_scores_packed(qi, w, pool, tables, past, this,
                                           cu, layer, interpret=False)
        shapes = [_bf16(tokens, 64, 128), ((tokens, 64), jnp.float32),
                  _bf16(2, 68608, 1, 16, 128), *lens,
                  ((batch + 1,), jnp.int32), ((), jnp.int32)]
        name = "paged_index_scores_chunk"
    elif launch == "index_decode":
        def fn(qi, w, pool, tables, past, this, layer):
            return pl_.index_scores_rows(qi, w, pool, tables, past, this,
                                         layer, interpret=False)
        shapes = [_bf16(batch, 64, 128), ((batch, 64), jnp.float32),
                  _bf16(2, 68608, 1, 16, 128), *lens, ((), jnp.int32)]
        name = "paged_index_scores_decode"
    else:
        n = tokens // 16 + 2 * batch

        def fn(pool, layer, pages, lo, hi, new):
            return pl_.write_latent_pages(pool, layer, pages, lo, hi, new,
                                          interpret=False)
        shapes = [_bf16(2, 68608, 1, 16, 128), ((), jnp.int32),
                  *[((n,), jnp.int32)] * 3, _bf16(n, 1, 16, 128)]
        name = "paged_cache_write_latent"
    assert name in _compile(fn, one_chip, *shapes).as_text()


@pytest.mark.parametrize("launch", ["index_decode", "index", "masked_decode"])
def test_run_copy_launches_compile_at_keye_shapes(one_chip, launch):
    """The three launches that take a key block in ONE copy where its
    pages lie side by side in the pool (PR 53: `block_runs` as one more
    prefetched scalar, `_run_copy` / `_run_copies` under `pl.when` beside
    the page-by-page copies), at `keye-vl2-30b-a3b-serve`'s shapes: 16
    sequences, tables of 4,096 entries (`max_len` 65,536), the index pool
    [4, 67584, 1, 16, 128] (a block of 128 pages of 4 KB in the one-row
    form, 32 in the chunk's, is the slice `pool[layer, p : p + pages, 0]`)
    and the heads' pools [4, 67584, 4, 16, 128] (64 pages of 16 KB,
    `pool[layer, p : p + 64]`). The index
    launches at `dots3-note-prev-serve`'s shapes are
    `test_sparse_latent_walks_compile`'s `index` and `index_decode`, which
    take the run plane too."""
    from paddle_tpu.ops.pallas import paged_attention_latent as pl_
    batch, tokens, entries = 16, 256, 4096
    lens = [((batch, entries), jnp.int32), ((batch,), jnp.int32),
            ((batch,), jnp.int32)]
    index_pool, heads_pool = (_bf16(4, 67584, 1, 16, 128),
                              _bf16(4, 67584, 4, 16, 128))
    if launch == "index_decode":
        def fn(qi, w, pool, tables, past, this, layer):
            return pl_.index_scores_rows(qi, w, pool, tables, past, this,
                                         layer, interpret=False)
        shapes = [_bf16(batch, 16, 128), ((batch, 16), jnp.float32),
                  index_pool, *lens, ((), jnp.int32)]
        name = "paged_index_scores_decode"
    elif launch == "index":
        def fn(qi, w, pool, tables, past, this, cu, layer):
            return pl_.index_scores_packed(qi, w, pool, tables, past, this,
                                           cu, layer, interpret=False)
        shapes = [_bf16(tokens, 16, 128), ((tokens, 16), jnp.float32),
                  index_pool, *lens, ((batch + 1,), jnp.int32),
                  ((), jnp.int32)]
        name = "paged_index_scores_chunk"
    else:
        def fn(q, kc, vc, tables, past, this, layer, mask):
            return pa.paged_attention(q, kc, vc, tables, past, this, 8,
                                      0.0884, interpret=False, layer=layer,
                                      mask=mask)
        shapes = [_bf16(batch, 4, 8, 128), heads_pool, heads_pool, *lens,
                  ((), jnp.int32),
                  ((batch, entries * 16 // 128, 4), jnp.uint32)]
        name = "paged_attention_decode_masked"
    assert name in _compile(fn, one_chip, *shapes).as_text()


@pytest.mark.parametrize("rows, keys", [
    (16, 65536), (256, 65536), (2048, 65536),       # Keye: decode, turn, chunk
    (32, 33280), (2048, 33280)])                    # dots3: decode, chunk
def test_the_selection_launch_compiles_at_both_index_cells_shapes(
        one_chip, rows, keys):
    """`index_select.select_bits` (PR 55: the search for the k-th
    order key and the ties' cut over a block of 8 rows' scores held in
    VMEM) at every executable's [rows, max_len] of the two index cells, k
    2,048: a chunk of a pass is 16 lane tiles at 65,536 keys and 13 at
    33,280 (260 tiles), and what the launch holds (the scores' block twice,
    the order keys once) stays under the 16 MiB a kernel has without asking
    for more."""
    from paddle_tpu.ops.pallas import index_select as ps

    def fn(scores, seen):
        return ps.select_bits(scores, seen, 2048, interpret=False)

    assert ps._vmem_bytes(keys) == 3 * 8 * 4 * keys + (4 << 20) < 16 << 20
    assert ps._chunk(keys) == 128 * (16 if keys == 65536 else 13)
    text = _compile(fn, one_chip, ((rows, keys), jnp.float32),
                    ((rows,), jnp.int32)).as_text()
    assert "index_select_bits" in text


def test_the_latent_walk_without_a_mask_is_the_kernel_it_was():
    """`latent_attention_packed` without a mask (Kimi's chunk walk, dots3's
    window walks and dense first chunk) launches with the parent's
    signature, written here: seven prefetched scalars, the query items and
    the pool, one output, five scratch buffers of the shapes they had; the
    masked walk takes one operand more, the items' mask (handed in as bits)
    in 8 bits cut into key blocks, and the same scratch."""
    from paddle_tpu.ops.pallas import paged_attention_latent as pl_
    batch, tokens, entries, heads, tq = 4, 64, 64, 128, 16
    S = jax.ShapeDtypeStruct
    shapes = [S((tokens, heads, 640), jnp.bfloat16),
              S((2, 64, 1, 16, 640), jnp.bfloat16),
              S((batch, entries), jnp.int32), S((batch,), jnp.int32),
              S((batch,), jnp.int32), S((batch + 1,), jnp.int32),
              S((), jnp.int32)]

    def launch(*mask):
        def fn(q, pool, tables, past, this, cu, layer, *mask):
            return pl_.latent_attention_packed(
                q, pool, tables, past, this, cu, 0.0625, layer, 512,
                interpret=False, **dict(zip(("mask",), mask)))
        call, = [e for e in jax.make_jaxpr(fn)(*shapes, *mask).jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        grid = call.params["grid_mapping"]
        refs = [(str(v.aval.dtype), v.aval.shape)
                for v in call.params["jaxpr"].invars]
        assert (grid.num_index_operands, grid.num_outputs) == (7, 1)
        return len(call.invars), grid.num_inputs, refs

    items = pl_.mixed_tokens(tokens, heads)
    assert items == tq
    rows = tq * heads
    scratch = [("bfloat16", (2, 32, 16, 640)), ("dma_sem", (2,)),
               ("float32", (rows, 512)), ("float32", (rows, 128)),
               ("float32", (rows, 128))]
    operands, inputs, refs = launch()
    assert (operands, inputs) == (9, 2)
    assert refs[7:] == [("bfloat16", (1, rows, 640)),
                        ("bfloat16", (2, 64, 1, 16, 640)),
                        ("bfloat16", (1, rows, 512))] + scratch
    operands, inputs, refs = launch(
        S((tokens, entries * 16 // 128, 4), jnp.uint32))
    assert (operands, inputs) == (10, 3)
    assert refs[8] == ("int8", (1, 2, tq, 512)) and refs[11:] == scratch


def test_dots3_depth5_ticks_fit_the_chip(one_chip, monkeypatch):
    """The cell `serve_sparse_latent_longctx` as the engine builds it on a
    TPU (`available` steered true), from the configuration file itself:
    both executables (a tick with a prefill chunk, 2,048 rows; a decode
    tick, 32 rows) compile for the described v5e with both latent pools
    and the index keys in their carry and 32 of 256 experts held, and the
    compiler counts each over 25 % and under the chip's 15.75 GiB, pinned
    where PR 45 read them, so that a later change of a pool's layout, of
    the selection's blocks or of `token_budget` cannot outgrow the chip
    unseen."""
    import json
    import os

    from benchmark.drivers import closed_loop_serve_sparse_latent as D
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L
    from paddle_tpu.ops.pallas import paged_attention_latent as pl_
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "dots3-note-prev-serve.json")) as f:
        file = json.load(f)
    cfg, e = D.dots3_config(file, jnp.bfloat16), file["engine"]
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    for module in (fa, pa, pl_):
        monkeypatch.setattr(module, "available", lambda: True)
    eng = PagedServingEngine(
        cfg, params, num_blocks=e["num_blocks"], block_size=e["block_size"],
        max_batch=e["max_batch"], token_budget=e["token_budget"],
        max_len=e["max_len"], pallas=True, pallas_ffn=False)
    shapes = jax.tree.map(lambda a: a.shape,
                          (eng._key_cache, eng._value_cache))
    assert shapes == (((2, 68608, 1, 16, 640), (3, 6144, 1, 16, 1152)),
                      ((2, 68608, 1, 16, 128), None))
    build, gib = eng._build_step, {}

    def compiled_not_run(tok_pad, B, *rest):
        fn = build(tok_pad, B, *rest)

        def tick(*args):
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), args)
            compiled = fn.lower(*abstract).compile()
            text = compiled.as_text()
            # a tick with a chunk runs the index walk, both dense walks
            # and the masked walk of a selecting chunk
            for name in ("paged_index_scores_chunk",
                         "paged_attention_latent_mixed",
                         "paged_attention_latent_masked"):
                assert (name in text) == (tok_pad == 2048)
            assert "paged_attention_latent_decode" in text
            # the one-row sequences of either tick score through the
            # one-row form; both launches copy their pages themselves
            # (PR 45): no relayout of the stacked index pool, no gather of
            # every sequence's 33,280 index keys
            assert "paged_index_scores_decode" in text
            assert "index_select_bits" in text     # (PR 55)
            assert "bf16[137216,2048]" not in text
            assert "bf16[32,33280,128]" not in text
            m = compiled.memory_analysis()
            gib[tok_pad] = (m.argument_size_in_bytes + m.output_size_in_bytes
                            + m.temp_size_in_bytes
                            - m.alias_size_in_bytes) / 2 ** 30
            return (jnp.zeros((B + len(eng._moe_fields),), jnp.int32),
                    args[1], args[2])
        return tick

    monkeypatch.setattr(eng, "_build_step", compiled_not_run)
    eng.submit(list(range(1, 70)), max_new_tokens=4)
    eng.step()                  # the prompt, one chunk
    eng.step()                  # a decode row
    assert set(gib) == {2048, 32}
    assert all(0.25 * 15.75 < g < 15.75 for g in gib.values()), gib
    # PR 45's readings, 14.37 and 11.57 (11.42 of them weights and pools;
    # PR 44's: 14.47 and 12.32, with a copy of the index pool and every
    # sequence's gathered index keys among the temporaries)
    assert gib[2048] < 14.4 and gib[32] < 11.6, gib
    print("dots3 depth-5 GiB by tok_pad:", gib)


@pytest.mark.parametrize("rows, slots", [(64, 128), (1024, 1024)])
def test_a_held_share_compiles_its_compact_form_at_kimi_widths(one_chip, rows,
                                                               slots):
    """A sparse layer of `kimi-k2.6-serve` (12 of 384 experts held, 8 a
    row) at the cell's two launches, a decode tick's 64 rows and a chunk
    tick's 1,024: one `conditional` whose branches run the grouped matmuls
    on `slots` rows (the compact form) and on all rows x 8 (the whole
    form), and neither branch, nor handing the stacked experts to them,
    makes anything of an expert matrix's size."""
    import re

    from benchmark.drivers import closed_loop_serve_latent as D
    from paddle_tpu.models import llama as L
    cfg = D.kimi_config(_kimi_file(), jnp.bfloat16)
    assert L.held_pair_slots(rows, cfg) == slots
    stack = jax.eval_shape(lambda k: L.init_params(cfg, k),
                           jax.random.PRNGKey(0))["blocks"][1]
    names = sorted(n for n in stack if n in (
        "router", "router_bias", "w1", "w3", "w2", "ws1", "ws3", "ws2"))

    def fn(h, valid, layer, *leaves):
        lp = {n: (w if n in ("w1", "w3", "w2") else w[0])
              for n, w in zip(names, leaves)}
        return L.routed_ffn_load(h, lp, cfg, valid, layer=layer)

    available = fa.available
    fa.available = lambda: True         # expert_form and interpret read it
    try:
        assert L.expert_form(cfg) == "sorted_gmm"
        text = _compile(fn, one_chip, _bf16(rows, 7168),
                        ((rows,), jnp.bool_), ((), jnp.int32),
                        *((stack[n].shape, stack[n].dtype) for n in names)
                        ).as_text()
    finally:
        fa.available = available
    assert len(re.findall(r" conditional\(", text)) == 1
    for m in (slots, rows * 8):
        assert re.search(rf"bf16\[{m},2048\]\S* custom-call\(", text), m
    made = re.findall(r"%(\S+) = bf16\[(?:5,)?12,(?:7168|2048),"
                      r"(?:2048|7168)\]\S* (\w[\w-]*)\(", text)
    assert {op for _, op in made} <= {"parameter", "bitcast",
                                      "get-tuple-element"}, made


@pytest.mark.parametrize("top_k", [0, 50])
def test_fused_sample_prep_compiles(one_chip, top_k):
    batch = 8
    assert fs.supported(batch, VOCAB)
    _compile(lambda l, t, p: fs.fused_sample_prep(l, t, p, top_k,
                                                  interpret=False),
             one_chip, ((batch, VOCAB), jnp.float32),
             ((batch,), jnp.float32), ((batch,), jnp.float32))


def test_r02_lse_blockspec_fails_tpu_lowering():
    """Deliberately rebuild the r02 bug — a rank-3 lse output whose block
    (1, 1, bq) puts a size-1 second-minor dim against H — and prove the
    TPU lowering catches it WITHOUT hardware. This guards the guard: if
    lowering for the TPU platform ever stops running Mosaic's
    block-mapping check, this test fails and the compiles above are known
    to be toothless."""
    B, H, T, bq = 2, 4, 512, 256

    def kernel(x_ref, o_ref):
        o_ref[0, 0] = jnp.max(x_ref[0, 0], axis=-1)

    def bad(x):
        return pl.pallas_call(
            kernel,
            grid=(B, H, T // bq),
            in_specs=[pl.BlockSpec((1, 1, bq, 128),
                                   lambda b, h, i: (b, h, i, np.int32(0)))],
            out_specs=pl.BlockSpec((1, 1, bq),
                                   lambda b, h, i: (b, h, i)),
            out_shape=jax.ShapeDtypeStruct((B, H, T), jnp.float32),
        )(x)

    x = jax.ShapeDtypeStruct((B, H, T, 128), jnp.float32)
    with pytest.raises(Exception, match="divisible|block shape"):
        jax.export.export(jax.jit(bad), platforms=["tpu"])(x)


def test_static_mirror_agrees_with_mosaic():
    """The CPU-side `_assert_mosaic_tileable` mirror rejects exactly the
    r02 spec too, so interpret-mode tests fail fast as well."""
    with pytest.raises(ValueError, match="tiling rule"):
        fa._assert_mosaic_tileable((1, 1, 256), (2, 4, 512), "lse output")
    # legal: trailing dim equals array dim
    fa._assert_mosaic_tileable((1, 1, 256, fa.LANES), (2, 4, 512, fa.LANES),
                               "lse output")


def test_keye_depth4_ticks_fit_the_chip(one_chip, monkeypatch):
    """The cell `serve_sparse_gqa_sessions_longctx` as the engine builds it
    on a TPU (`available` steered true), from the configuration file
    itself: its three executables (a tick with a chunk, 2,048 rows; a tick
    with a turn's new rows, 256; a decode tick, 16) compile for the
    described v5e with the three page arrays (keys, values, index keys in
    whole lanes) in their carry and 16 of 128 experts held, the masked
    walks and the index's two launches at a 64-wide key in 128 lanes among
    them, and the compiler counts each over 25 % and under the chip's
    15.75 GiB; the page copy that carries the index keys compiles too."""
    import json
    import os

    from benchmark.drivers import closed_loop_sparse_sessions as D
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L
    from paddle_tpu.ops.pallas import paged_attention_latent as pl_
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "keye-vl2-30b-a3b-serve.json")) as f:
        file = json.load(f)
    cfg, e = D.keye_config(file, jnp.bfloat16), file["engine"]
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    for module in (fa, pa, pl_):
        monkeypatch.setattr(module, "available", lambda: True)
    eng = PagedServingEngine(
        cfg, params, num_blocks=e["num_blocks"], block_size=e["block_size"],
        max_batch=e["max_batch"], token_budget=e["token_budget"],
        max_len=e["max_len"], pallas=True, pallas_ffn=False)
    shapes = jax.tree.map(lambda a: a.shape, (
        eng._key_cache, eng._value_cache, eng._index_cache))
    assert shapes == ((4, 67584, 4, 16, 128), (4, 67584, 4, 16, 128),
                      (4, 67584, 1, 16, 128))
    assert eng.kv_page_bytes == 4 * 16 * 2304 and eng._row_pads == (256, 2048)
    build, gib = eng._build_step, {}

    def compiled_not_run(tok_pad, B, *rest):
        fn = build(tok_pad, B, *rest)

        def tick(*args, **kw):
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip),
                (args, kw))
            compiled = fn.lower(*abstract[0], **abstract[1]).compile()
            text = compiled.as_text()
            # a one-row sequence reads in the decode launch's forms in
            # every tick; a tick with new rows runs the chunk's index walk
            # and the masked mixed walk beside them
            for name in ("paged_attention_mixed_masked",
                         "paged_index_scores_chunk"):
                assert (name in text) == (tok_pad > 16), name
            assert "paged_attention_decode_masked" in text
            assert "paged_index_scores_decode" in text
            assert "index_select_bits" in text     # (PR 55)
            # no layout change of a pool, no copy of one
            assert "bf16[4,67584,4,16,128]{4,2,3,1,0" not in text
            m = compiled.memory_analysis()
            gib[tok_pad] = (m.argument_size_in_bytes + m.output_size_in_bytes
                            + m.temp_size_in_bytes
                            - m.alias_size_in_bytes) / 2 ** 30
            return (jnp.zeros((B + len(eng._moe_fields),), jnp.int32),
                    args[1], args[2], kw["index_cache"])
        return tick

    monkeypatch.setattr(eng, "_build_step", compiled_not_run)
    eng.submit(list(range(1, 2100)), max_new_tokens=3)
    eng.step()                  # the prompt's first chunk, 2,048 rows
    eng.step()                  # its last 51 rows: the 256-row executable
    eng.step()                  # a decode row
    assert set(gib) == {2048, 256, 16}
    assert all(0.25 * 15.75 < g < 15.75 for g in gib.values()), gib
    print("keye depth-4 GiB by tok_pad:", gib)
    # the copy-on-write page copy, its index keys with it (built, not run:
    # here it would rewrite 10 GB of pools on the CPU)
    eng._copy_blocks([])
    pools = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (eng._key_cache, eng._value_cache, eng._index_cache))
    idx = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    m = eng._copy_fn.lower(pools[0], pools[1], None, None, idx, idx,
                           pools[2]).compile().memory_analysis()
    copy_gib = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes) / 2 ** 30
    print("keye page copy GiB:", copy_gib)
    assert copy_gib < 15.75 - 1.0       # beside the weights


def test_granite_depth40_ticks_fit_the_chip(one_chip, monkeypatch):
    """The cell `serve_ssm_chat_decode64` as the engine builds it on a TPU
    (`available` steered true), from the configuration file itself: both
    executables (a tick with a prefill chunk, 512 rows: the state-space
    layers' convolution, one-row update AND chunked scan; a decode tick, 64
    rows: no scan) compile for the described v5e at the published widths
    and all 40 layers, the 4 attention layers' 64-wide heads in 128 lanes through the
    whole-page walks, with the float32 state pool of 65 slots donated and
    carried, and the compiler counts each over 25 % and under the chip's
    15.75 GiB with room for the float32 check."""
    import json
    import os
    import re

    from benchmark.drivers import closed_loop_serve_ssm as D
    from paddle_tpu.inference.serving import PagedServingEngine
    from paddle_tpu.models import llama as L
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-micro-serve.json")) as f:
        file = json.load(f)
    cfg, e = D.granite_config(file, jnp.bfloat16), file["engine"]
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert params["blocks"][0]["w_in"].shape == (36, 2048, 8448)
    assert params["blocks"][1]["wq"].shape == (4, 2048, 2048)
    assert "lm_head" not in params and cfg.num_params() == 3_191_396_096
    for module in (fa, pa):
        monkeypatch.setattr(module, "available", lambda: True)
    # the pools as shapes: 4.97 GB of zeros are not made here
    pools = L.ssm_state_pools
    monkeypatch.setattr(L, "ssm_state_pools",
                        lambda *a: jax.eval_shape(lambda: pools(*a)))
    eng = PagedServingEngine(
        cfg, params, num_blocks=e["num_blocks"], block_size=e["block_size"],
        max_batch=e["max_batch"], token_budget=e["token_budget"],
        max_len=e["max_len"], pallas=True, pallas_ffn=False)
    # a head of 64 in 128 lanes: the whole-page walks, no pool re-laid out
    assert eng._key_cache.shape == (4, 7680, 8, 16, 128)
    assert [p.shape for p in eng._state] == [(36, 65, 64, 64, 128),
                                             (36, 65, 3 * 4352)]
    assert eng._state[0].dtype == jnp.float32 and eng._rope_emb == ()
    assert eng.state_slot_bytes * 65 == 4_968_437_760
    build, gib = eng._build_step, {}

    def compiled_not_run(tok_pad, B, *rest):
        fn = build(tok_pad, B, *rest)

        def tick(*args, **kw):
            abstract, abstract_kw = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip),
                (args, kw))
            compiled = fn.lower(*abstract, **abstract_kw).compile()
            text = compiled.as_text()
            assert ("ssm_scan" in text) == (tok_pad == 512)
            assert "ssm_step" in text and "ssm_conv" in text
            # the one-row update is the launch of `ops/pallas/ssm_step.py`,
            # in place on the donated pool
            assert "ssm_state_step" in text
            assert "paged_cache_write" in text
            assert ("paged_attention_mixed" in text) == (tok_pad == 512)
            assert ("paged_attention_decode" in text) == (tok_pad == 64)
            # no copy of a page pool: the device's own layout of
            # [.., 16, 128] is the kernels'
            assert not re.search(
                r"= bf16\[4,7680,8,16,128\]\S* copy\(", text)
            m = compiled.memory_analysis()
            gib[tok_pad] = (m.argument_size_in_bytes + m.output_size_in_bytes
                            + m.temp_size_in_bytes
                            - m.alias_size_in_bytes) / 2 ** 30
            return (jnp.zeros((B,), jnp.int32), args[1], args[2],
                    kw["state"])
        return tick

    monkeypatch.setattr(eng, "_build_step", compiled_not_run)
    eng.submit(list(range(1, 70)), max_new_tokens=4)
    eng.step()                  # the prompt, one chunk
    eng.step()                  # a decode row
    assert set(gib) == {512, 64}
    assert all(0.25 * 15.75 < g < 15.75 for g in gib.values()), gib
    print("Granite depth-40 GiB by tok_pad:", gib)
