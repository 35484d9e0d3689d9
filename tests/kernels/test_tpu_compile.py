"""Compile the main-path Pallas kernels for a described (not attached) v5e.

The interpret-mode parity tests cannot see what the chip's compiler refuses:
a block whose last two dims are not (8, 128)-aligned, a kernel over the
scoped-VMEM limit. libtpu compiles for a topology that is only described, so
these cases run on the CPU sandbox at the widths ``chip_smoke.py`` reaches
(gpt2-125m training, opt-1.3b serving). Nothing executes — results are the
parity tests' job.
"""

import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops import (decode_attention, flash_attention,
                               fused_layer_norm, kda_decode_step,
                               mamba2_decode_step, moe_grouped_matmul,
                               paged_decode_attention,
                               paged_prefill_attention)
from deepspeed_tpu.ops import mamba1_chunk_scan, mamba1_decode_step
from deepspeed_tpu.ops.paged_decode_attention import (
    latent_decode_attention, paged_attention)
from deepspeed_tpu.ops.moe_grouped_matmul import max_tiles, tile_rows

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
REPO = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device; the persistent compile cache is off while
    the module runs (such a compile is written to it but cannot be read back
    without a chip — the next run would warn and compile again)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # libtpu lets one process at a time load it unless told otherwise; with
    # no chip attached several pytest workers can describe one side by side
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"TPU topology cannot be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _flash(b, t, h, d, grad, kv_heads=None):
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(F32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    return fn, ([((b, t, h, d), BF16)]
                + [((b, t, kv_heads or h, d), BF16)] * 2)


def _layer_norm(rows, t, e, grad):
    def fwd(x, s, b):
        return fused_layer_norm(x, s, b, 1e-5, False)

    def loss(x, s, b):
        return fwd(x, s, b).astype(F32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    return fn, [((rows, t, e), BF16), ((e,), F32), ((e,), F32)]


def _paged_decode(rows, heads, d, block, maxb, kv_heads=None):
    # layer: a traced scalar
    arena = ((3, 1024, block, (kv_heads or heads) * d), BF16)
    return (paged_decode_attention,
            [((rows, heads, d), BF16), arena, arena, ((), I32),
             ((rows, maxb), I32), ((rows,), I32)])


def _latent_decode(rows, heads, width, values, block, maxb):
    """The one-pool walk: q as wide as a page, the values its first lanes."""
    def walk(q, arena, layer, table, lengths):
        return latent_decode_attention(q, arena, layer, table, lengths,
                                       values, 192 ** -0.5)

    return (walk, [((rows, heads, width), BF16),
                   ((3, 1024, block, width), BF16), ((), I32),
                   ((rows, maxb), I32), ((rows,), I32)])


def _paged_prefill(chunk, heads, d, block, maxb, rows=1, kv_heads=None):
    # start and the rows' real lengths, as ``paged_attention`` hands them
    arena = ((3, 1024, block, (kv_heads or heads) * d), BF16)
    return (paged_prefill_attention,
            [((rows, chunk, heads, d), BF16), arena, arena, ((), I32),
             ((rows, maxb), I32), ((rows,), I32), ((rows,), I32)])


def _dense_decode(b, t, heads, d):
    cache = ((b, t, heads, d), BF16)
    return (decode_attention,
            [((b, heads, d), BF16), cache, cache, ((b, t), I32)])


def _kda_decode(rows, heads, d, layers=3):
    # the pool of a slot a row and one scratch, the layer a traced scalar
    vec = ((rows, heads, d), F32)
    return (kda_decode_step,
            [vec, vec, vec, vec, ((rows, heads), F32),
             ((layers, rows + 1, heads, d, d), F32), ((), I32),
             ((rows,), I32)])


def _mamba2_decode(rows, heads, p, groups, n, layers=5):
    # the pool of a slot a row and one scratch, the layer a traced scalar
    return (mamba2_decode_step,
            [((rows, heads, p), F32), ((rows, heads), F32), ((heads,), F32),
             ((rows, groups, n), F32), ((rows, groups, n), F32),
             ((layers, rows + 1, groups, n, heads // groups * p), F32),
             ((), I32), ((rows,), I32)])


def _mamba1_decode(rows, inner, n, layers=9):
    # the pool of a slot a row and one scratch, the layer a traced scalar
    return (mamba1_decode_step,
            [((rows, inner), F32), ((rows, inner), F32), ((n, inner), F32),
             ((rows, n), F32), ((rows, n), F32),
             ((layers, rows + 1, n, inner), F32), ((), I32), ((rows,), I32)])


def _mamba1_chunk(chunk, inner, n):
    return (mamba1_chunk_scan,
            [((1, chunk, inner), F32), ((1, chunk, inner), F32),
             ((n, inner), F32), ((1, chunk, n), F32), ((1, chunk, n), F32),
             ((1, n, inner), F32)])


def _paged_window(queries, rows, heads, d, block, maxb, kv_heads, window,
                  monkeypatch):
    """``paged_attention`` under a window on its kernel branch: the decode
    walk (one query a row) or the prefill kernel, over a ring's pool."""
    from deepspeed_tpu.ops import registry

    monkeypatch.setattr(registry, "kernels_active", lambda: True)
    arena = ((8, 3121, block, kv_heads * d), BF16)

    def fn(q, k, v, layer, table, positions):
        return paged_attention(q, k, v, layer, table, positions,
                               scale=0.125, window=window,
                               name="window_decode_attention")

    return fn, [((rows, queries, heads, d), BF16), arena, arena, ((), I32),
                ((rows, maxb), I32), ((rows, queries), I32)]


def _grouped_matmul(tokens, top_k, experts, k, n, gated=False):
    """The expert matmul of `tokens` x `top_k` assignments laid out in
    tiles, as parallel/moe.py calls it: one matrix an expert out of a
    model's `(L, E, K, N)` stack at a traced layer, or (`gated`) a gated
    expert's gate and up in one call."""
    tm = tile_rows(tokens * top_k, experts, BF16)
    tiles = max_tiles(tokens * top_k, experts, tm)
    stack = ((GMM_LAYERS, experts, k, n), BF16)

    def fn(lhs, tile_expert, used, layer, up, *gate):
        return moe_grouped_matmul(lhs, up, tile_expert, used, layer,
                                  **({"gate": gate[0]} if gated else {}))

    return (fn, [((tiles * tm, k), BF16), ((tiles,), I32), ((1,), I32),
                 ((), I32)] + [stack] * (2 if gated else 1))


GMM_LAYERS = 3


# gpt2-125m: 12 heads x 64, hidden 768, seq 1024, micro-batch 32.
# opt-1.3b: 32 heads x 64, hidden 2048, seq 2048; serving block 16,
# chunk 256, 16 decode rows, 128 blocks per sequence.
# olmoe-1b-7b: 16 heads x 128, hidden 2048, 8 of 64 experts of width 1024;
# served like opt-1.3b.
# solar-open2-250b as one of 8 chips: 64 decode rows; 64 heads over 8 KV heads
# of 128; 64 linear-attention heads of 128 x 128 state; a row's 8
# assignments of which an eighth reach the 40 held experts of width 1280
# nemotron-3-super as one of 8 chips: 64 decode rows; 128 state-space heads
# of 64 in 8 groups of state 128; 32 heads over 2 KV heads of 128; a row's 22
# assignments of which an eighth reach the 64 held experts, 1024 x 2688
# phi-4-mini-flash-reasoning whole: 64 decode rows of up to 2,560 tokens; a
# Mamba-1 state of 16 x 5,120 a (row, layer); differential attention's pairs
# folded into 40 query heads over 10 key-value heads of 128
# longcat-flash-chat as one of 32 chips: 32 decode rows of up to 5,120 tokens;
# the absorbed latent read is the one-pool walk: ONE key-value head as wide
# as a page, 576 values in 640 lanes (at 576 Mosaic refuses the page's copy:
# "slice shape along dimension 3 must be aligned to tiling (128)"), of which
# the first 512 are the values
# lfm2-8b-a1b's first stage: 16 decode rows of up to 8,320 tokens (a table
# of 520 pages) and chunks of 1,024; 32 heads over 8 key-value heads of 64
# (pages 512 values wide); 4 of 32 experts of 2,048 x 1,792 (1,792 = 14 x
# 128, no multiple of 512) a token: 128 rows an expert in a chunk, 16 rows'
# 64 assignments in a step
CASES = {
    "paged-decode-lfm2-8b-a1b":
        lambda: _paged_decode(16, 32, 64, 16, 520, kv_heads=8),
    "paged-prefill-lfm2-8b-a1b":
        lambda: _paged_prefill(1024, 32, 64, 16, 520, kv_heads=8),
    "moe-up-prefill-lfm2-8b-a1b":
        lambda: _grouped_matmul(1024, 4, 32, 2048, 1792),
    "moe-gated-prefill-lfm2-8b-a1b":
        lambda: _grouped_matmul(1024, 4, 32, 2048, 1792, gated=True),
    "moe-down-prefill-lfm2-8b-a1b":
        lambda: _grouped_matmul(1024, 4, 32, 1792, 2048),
    "moe-up-decode-lfm2-8b-a1b":
        lambda: _grouped_matmul(16, 4, 32, 2048, 1792),
    "moe-gated-decode-lfm2-8b-a1b":
        lambda: _grouped_matmul(16, 4, 32, 2048, 1792, gated=True),
    "moe-down-decode-lfm2-8b-a1b":
        lambda: _grouped_matmul(16, 4, 32, 1792, 2048),
    "latent-decode-longcat-flash":
        lambda: _latent_decode(32, 64, 640, 512, 16, 320),
    "moe-up-decode-longcat-flash":
        lambda: _grouped_matmul(32, 12, 16, 6144, 2048),
    "moe-gated-decode-longcat-flash":
        lambda: _grouped_matmul(32, 12, 16, 6144, 2048, gated=True),
    "moe-down-decode-longcat-flash":
        lambda: _grouped_matmul(32, 12, 16, 2048, 6144),
    "mamba1-decode-phi-4-mini-flash":
        lambda: _mamba1_decode(64, 5120, 16),
    "mamba1-chunk-phi-4-mini-flash": lambda: _mamba1_chunk(256, 5120, 16),
    "paged-decode-phi-4-mini-flash":
        lambda: _paged_decode(64, 40, 128, 16, 160, kv_heads=10),
    "paged-prefill-phi-4-mini-flash":
        lambda: _paged_prefill(256, 40, 128, 16, 160, kv_heads=10),
    "mamba2-decode-nemotron-3-super":
        lambda: _mamba2_decode(64, 128, 64, 8, 128),
    "paged-decode-nemotron-3-super":
        lambda: _paged_decode(64, 32, 128, 16, 128, kv_heads=2),
    "moe-up-decode-nemotron-3-super":
        lambda: _grouped_matmul(64, 22, 64, 1024, 2688),
    "moe-down-decode-nemotron-3-super":
        lambda: _grouped_matmul(64, 22, 64, 2688, 1024),
    "kda-decode-solar-open2": lambda: _kda_decode(64, 64, 128),
    "paged-decode-solar-open2":
        lambda: _paged_decode(64, 64, 128, 16, 128, kv_heads=8),
    "paged-prefill-solar-open2":
        lambda: _paged_prefill(256, 64, 128, 16, 128, kv_heads=8),
    "moe-up-decode-solar-open2":
        lambda: _grouped_matmul(64, 8, 40, 4096, 1280),
    "moe-gated-decode-solar-open2":
        lambda: _grouped_matmul(64, 8, 40, 4096, 1280, gated=True),
    "moe-down-decode-solar-open2":
        lambda: _grouped_matmul(64, 8, 40, 1280, 4096),
    "paged-decode-olmoe-1b-7b": lambda: _paged_decode(16, 16, 128, 16, 128),
    "paged-prefill-olmoe-1b-7b": lambda: _paged_prefill(256, 16, 128, 16, 128),
    "moe-up-decode-olmoe-1b-7b":
        lambda: _grouped_matmul(16, 8, 64, 2048, 1024),
    "moe-gated-decode-olmoe-1b-7b":
        lambda: _grouped_matmul(16, 8, 64, 2048, 1024, gated=True),
    "moe-down-decode-olmoe-1b-7b":
        lambda: _grouped_matmul(16, 8, 64, 1024, 2048),
    "moe-up-prefill-olmoe-1b-7b":
        lambda: _grouped_matmul(256, 8, 64, 2048, 1024),
    "moe-gated-prefill-olmoe-1b-7b":
        lambda: _grouped_matmul(256, 8, 64, 2048, 1024, gated=True),
    "moe-down-prefill-olmoe-1b-7b":
        lambda: _grouped_matmul(256, 8, 64, 1024, 2048),
    "flash-fwd-gpt2-125m": lambda: _flash(32, 1024, 12, 64, grad=False),
    "flash-bwd-gpt2-125m": lambda: _flash(32, 1024, 12, 64, grad=True),
    "flash-fwd-opt-1.3b": lambda: _flash(4, 2048, 32, 64, grad=False),
    "flash-bwd-opt-1.3b": lambda: _flash(4, 2048, 32, 64, grad=True),
    # shapes no cell trains: the strip walk's static slices and the tile rule
    # at head size 128 (olmoe-1b-7b), under GQA (8 query heads over 2) and
    # at a sequence of four tiles a side
    "flash-bwd-olmoe-1b-7b": lambda: _flash(2, 2048, 16, 128, grad=True),
    "flash-bwd-gqa": lambda: _flash(2, 2048, 8, 128, grad=True, kv_heads=2),
    "flash-bwd-seq-4096": lambda: _flash(2, 4096, 32, 64, grad=True),
    "layernorm-fwd-gpt2-125m": lambda: _layer_norm(32, 1024, 768, False),
    "layernorm-bwd-gpt2-125m": lambda: _layer_norm(32, 1024, 768, True),
    "layernorm-fwd-opt-1.3b": lambda: _layer_norm(8, 1024, 2048, False),
    "layernorm-bwd-opt-1.3b": lambda: _layer_norm(8, 1024, 2048, True),
    "paged-decode-gpt2-125m": lambda: _paged_decode(16, 12, 64, 16, 64),
    "paged-decode-opt-1.3b": lambda: _paged_decode(16, 32, 64, 16, 128),
    "paged-prefill-gpt2-125m": lambda: _paged_prefill(256, 12, 64, 16, 64),
    "paged-prefill-opt-1.3b": lambda: _paged_prefill(256, 32, 64, 16, 128),
    # the whole table of 2,048 tokens under GQA: 32 heads over 8 KV heads
    "paged-decode-gqa-full-table":
        lambda: _paged_decode(16, 32, 128, 16, 128, kv_heads=8),
    "paged-prefill-gqa": lambda: _paged_prefill(256, 32, 128, 16, 128,
                                                kv_heads=8),
    # the speculative verify step: 16 rows of 5 slots
    "paged-prefill-verify-opt-1.3b":
        lambda: _paged_prefill(5, 32, 64, 16, 128, rows=16),
    "dense-decode-gpt2-125m": lambda: _dense_decode(8, 1024, 12, 64),
    "dense-decode-opt-1.3b": lambda: _dense_decode(8, 2048, 32, 64),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=v5e) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    if "layernorm-bwd" not in case:   # the norm's backward is plain jnp
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("queries,rows", [(1, 64), (256, 1)],
                         ids=["decode", "prefill"])
def test_window_walk_compiles_for_v5e(v5e, monkeypatch, queries, rows):
    """The paged kernels' window form at phi-4-mini-flash-reasoning's
    shapes: a window of 512 keys over a ring of 48 pages a row, the table
    cut to the pages a window and its queries can span."""
    fn, shapes = _paged_window(queries, rows, 40, 128, 16, 160, 10, 512,
                               monkeypatch)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=v5e) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("window_decode_attention" if queries == 1
            else "paged_prefill_attention") in text


def _two_widths(queries, rows, kv_heads, window, monkeypatch):
    """``paged_attention`` on its kernel branch at mimo-v2-flash's shapes:
    64 query heads, keys 192 and values 128 wide; a full layer's 4 key-value
    heads over pages, or a window layer's 8 over a ring of 72 pages a slot
    with a learned sink a head."""
    from deepspeed_tpu.ops import registry

    monkeypatch.setattr(registry, "kernels_active", lambda: True)
    blocks = 20481 if window is None else 1 + 33 * 72
    pools = 2 if window is None else 5
    name = ("full_kv_decode_attention" if window is None
            else "window_decode_attention")

    def fn(q, k, v, layer, table, positions, sink):
        return paged_attention(q, k, v, layer, table, positions,
                               scale=192 ** -0.5, window=window, name=name,
                               **({} if window is None else {"sink": sink}))

    return fn, [((rows, queries, 64, 192), BF16),
                ((pools, blocks, 16, kv_heads * 192), BF16),
                ((pools, blocks, 16, kv_heads * 128), BF16), ((), I32),
                ((rows, 640), I32), ((rows, queries), I32),
                ((64,), jnp.float32)]


@pytest.mark.parametrize("queries,rows", [(1, 32), (1024, 1)],
                         ids=["decode", "prefill"])
@pytest.mark.parametrize("kv_heads,window", [(4, None), (8, 128)],
                         ids=["full", "window-sink"])
def test_walks_of_two_widths_compile_for_v5e(v5e, monkeypatch, kv_heads,
                                             window, queries, rows):
    """Keys 192 and values 128 wide under 64 query heads: the decode walk
    with the values' own lane and diag masks, and the prefill kernel over
    slabs of two heads (384 and 256 lanes), its chunk of 1,024 queries gone
    down as rows of 256 (16 heads a key-value head) or of 128 (8)."""
    fn, shapes = _two_widths(queries, rows, kv_heads, window, monkeypatch)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=v5e) for s, dt in shapes]
    lowered = jax.jit(fn).lower(*args)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    if queries == 1:
        assert ("window_decode_attention" if window
                else "full_kv_decode_attention") in text
        return
    assert "paged_prefill_attention" in text
    asked = [int(n) for n in re.findall(
        r'scoped_memory_configs\\22: \[\{\\22memory_space\\22:1, '
        r'\\22offset\\22: 0, \\22size\\22: (\d+)', lowered.as_text())]
    assert len(asked) == 1 and 16 << 20 <= asked[0] <= 100 << 20
    parts = 4 if window is None else 8
    assert f"bf16[{parts},{1024 // parts},12288]" in lowered.as_text() \
        or f"{parts}x{1024 // parts}x12288xbf16" in lowered.as_text()


# ---------------------------------------------------------------------------
# the form of a walk's copies, read off the kernel's own text: what sets a
# decode walk's pace is its descriptors, and a loop around a page's start
# doubles them (PERF.md section 6, PRs 64 and 66)
# ---------------------------------------------------------------------------


def _kernel_module(fn, args):
    """(the ONE Mosaic kernel ``fn`` lowers to, parsed; the context it lives
    in)."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    text = jax.jit(fn).lower(*args).as_text()
    bodies = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)
    assert len(bodies) == 1, len(bodies)
    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True
    with context:
        return ir.Module.parse(base64.b64decode(bodies[0])), context


def _dma_census(fn, args):
    """The kernel ``fn`` lowers to, as Mosaic has it: for each DMA start and
    each DMA wait, the loops around it (innermost first, by identity) and
    the pages its first operand spans."""
    from jax._src.lib.mlir import ir

    starts, waits, met = [], [], []

    def visit(op, loops):
        name = op.operation.name
        if name.endswith(("scf.for", "scf.while")):
            met.append(name)
            loops = (len(met),) + loops
        if name.endswith("tpu.enqueue_dma"):
            starts.append(loops)
        if name.endswith("tpu.wait_dma2"):
            shape = ir.MemRefType(op.operation.operands[1].type).shape
            waits.append((loops, shape[0] if len(shape) == 3 else 1))
        for region in op.operation.regions:
            for block in region:
                for inner in block:
                    visit(inner, loops)

    module, context = _kernel_module(fn, args)
    with context:
        for op in module.body:
            visit(op, ())
    return starts, waits


def _block_census(fn, args):
    """Of a kernel whose copies are the pipeline's (its `BlockSpec`s): (the
    grid, the block of each pipelined operand and result, in order), as the
    kernel's own text declares them."""
    module, context = _kernel_module(fn, args)
    with context:
        text = module.operation.get_asm(enable_debug_info=False)
    grid = re.search(r"iteration_bounds = array<i64: ([0-9, ]+)>", text)
    blocks = re.findall(r"window_bounds = array<i64: ([0-9, ]+)>", text)
    as_ints = lambda found: tuple(int(n) for n in found.split(","))
    return as_ints(grid.group(1)), [as_ints(b) for b in blocks]


def _a_page_a_turn(census, sides):
    """Of a census of copies, each the loops around it: (the innermost loops
    that hold ONE page's copies, ``sides`` of them; the copies in no such
    loop)."""
    inner = {}
    for loops in census:
        inner[loops[:1]] = inner.get(loops[:1], 0) + 1
    paged = [loop for loop, n in inner.items() if loop and n == sides]
    return paged, [loops for loops in census if loops[:1] not in paged]


# (the walk, the pages a tile of it holds, the sides a page is copied for,
# the sites that start a tile's copies: a row's own first tile, the next
# tile and the next row's first, the last two once a buffer where a tile's
# body is built once a buffer; the sites that wait for one)
DECODE_WALKS = {
    # pages of 2,048 lanes are 64 KiB a side: their bytes set the walk's
    # pace and not their descriptors (``_unrolls_whole_tiles``), so the
    # loops alone, no unrolled start and no wait larger than a page
    # (``pages`` 0)
    "opt-1.3b": (lambda mp: _paged_decode(16, 32, 64, 16, 128), 0, 2, 3, 1),
    "ouro-2.6b": (lambda mp: _paged_decode(16, 16, 128, 16, 20), 0, 2, 3, 1),
    "solar-open2-gqa": (
        lambda mp: _paged_decode(64, 64, 128, 16, 128, kv_heads=8),
        16, 2, 3, 1),
    "nemotron-3-super": (
        lambda mp: _paged_decode(64, 32, 128, 16, 128, kv_heads=2),
        16, 2, 3, 1),
    "phi-4-mini-flash": (
        lambda mp: _paged_decode(64, 40, 128, 16, 160, kv_heads=10),
        16, 2, 3, 1),
    "phi-4-mini-flash-window": (
        lambda mp: _paged_window(1, 64, 40, 128, 16, 160, 10, 512, mp),
        16, 2, 3, 1),
    "longcat-flash-latent": (
        lambda mp: _latent_decode(32, 64, 640, 512, 16, 320), 32, 1, 5, 2),
}


@pytest.mark.parametrize("walk", sorted(DECODE_WALKS))
def test_a_decode_walk_starts_a_whole_tiles_pages_side_by_side(
        v5e, monkeypatch, walk):
    """No loop around a WHOLE tile's starts and ONE wait a side for it: at
    every site that starts a tile's copies the kernel holds the tile's P
    starts a side unrolled (beside the loop over a row's tiles, where the
    site lies in it, they stand in no loop) and one loop of a page a turn
    for a row's last tile; at every site that waits, one wait a side as
    large as the tile and one loop of a page a turn. A two-pool walk over
    pages of 64 KiB a side keeps the loops alone."""
    build, pages, sides, start_sites, wait_sites = DECODE_WALKS[walk]
    fn, shapes = build(monkeypatch)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=v5e) for s, dt in shapes]
    starts, waits = _dma_census(fn, args)

    loops_of_starts, unrolled = _a_page_a_turn(starts, sides)
    assert len(loops_of_starts) == start_sites
    assert len(unrolled) == start_sites * sides * pages
    assert max((len(loops) for loops in unrolled), default=0) <= 1
    assert sorted(n for _, n in waits) == (
        [1] * (wait_sites * sides) + [pages] * (wait_sites * sides * (pages > 0)))
    loops_of_waits, _ = _a_page_a_turn([l for l, n in waits if n == 1],
                                       sides)
    assert len(loops_of_waits) == wait_sites
    assert not {l[:1] for l, n in waits if n == pages} & set(loops_of_waits)


def test_the_chunk_kernel_keeps_its_loops_of_starts(v5e):
    """``paged_prefill_attention`` shares the walk's helpers and not the
    decode walks' forms: its copies are noise beside its products, so it
    keeps a loop of starts and a loop of waits, a page (k and v) a turn, and
    its text stays short: ONE tile body since PR 69 (the sub-blocks of a
    tile say which of them take a mask, not the tile), so three sites that
    start a tile (a row's first, the next, the next row's first) and one
    that waits, k and v each."""
    fn, shapes = _paged_prefill(256, 32, 64, 16, 128)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=v5e) for s, dt in shapes]
    starts, waits = _dma_census(fn, args)
    assert len(starts) == 6 and len(waits) == 2
    for census in (starts, [loops for loops, _ in waits]):
        paged, unrolled = _a_page_a_turn(census, 2)
        assert len(paged) == len(census) // 2 and not unrolled
    assert {n for _, n in waits} == {1}


@pytest.mark.parametrize("case,most_mib", [
    ("paged-prefill-opt-1.3b", 48), ("paged-prefill-lfm2-8b-a1b", 100),
    ("paged-prefill-verify-opt-1.3b", 24)])
def test_the_chunk_kernel_asks_for_the_vmem_it_holds(v5e, case, most_mib):
    """What ``paged_prefill_attention`` asks for (``vmem_limit_bytes``, the
    kernel's scoped memory in its text) at the two cells' shapes and the
    verify step's: its tiles of 1,024 keys, q block-diagonal, the
    accumulator and the lane-replicated statistics of every head, and a
    visit's blocks of scores, under the chip's 128 MiB with room to spare;
    and the compiler takes it (a kernel that holds more than it asked for
    is refused here, as the verify step's was at 18.4 MiB)."""
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=v5e) for s, dt in shapes]
    lowered = jax.jit(fn).lower(*args)
    asked = [int(n) for n in re.findall(
        r'scoped_memory_configs\\22: \[\{\\22memory_space\\22:1, '
        r'\\22offset\\22: 0, \\22size\\22: (\d+)', lowered.as_text())]
    assert len(asked) == 1 and 16 << 20 <= asked[0] <= most_mib << 20
    assert "tpu_custom_call" in lowered.compile().as_text()


# ---------------------------------------------------------------------------
# the expert matmul's weight block, read off the kernel's own text: a touched
# expert's matrix is ONE block, one contiguous copy, wherever the call's
# weight buffers fit their share of VMEM (PERF.md section 6, PR 68)
# ---------------------------------------------------------------------------

# case: the columns of its weight block (all of N: the whole matrix)
GROUPED_BLOCKS = {
    case: None for case in CASES if case.startswith("moe-")}
# 6,144 x 2,048 is 24 MiB a matrix: two of them twice over do not fit, so
# gate and up come as column blocks of 1,024 (12 MiB, runs of 32 KiB)
GROUPED_BLOCKS["moe-gated-decode-longcat-flash"] = 1024


@pytest.mark.parametrize("case", sorted(GROUPED_BLOCKS))
def test_a_touched_experts_matrix_is_one_block(v5e, case):
    """Every call of the grouped matmul at every cell's shape: the pipeline
    makes the copies (no hand-made DMA), the weight block is the whole
    `(K, N)` matrix of ONE expert of ONE layer, so the grid is `(1, TILES)`
    and a touched expert's matrix moves once a call (the block index
    changes only with the tile's expert); a gated call takes the row tile
    ONCE beside two such blocks and writes one tile; the stack is the
    call's operand where it lies, no layer's bank is sliced out of it."""
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=v5e) for s, dt in shapes]
    assert _dma_census(fn, args) == ([], [])
    (rows, K), (tiles,) = shapes[0][0], shapes[1][0]
    _, E, _, N = shapes[4][0]
    tm, matrices = rows // tiles, len(shapes) - 4
    assert matrices == (2 if "gated" in case else 1)
    tn = GROUPED_BLOCKS[case] or N
    grid, blocks = _block_census(fn, args)
    assert grid == (N // tn, tiles)
    assert blocks == ([(tm, K)] + [(1, 1, K, tn)] * matrices + [(tm, tn)])
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "moe_grouped_matmul" in text
    assert f"bf16[{E},{K},{N}]" not in text
    assert f"bf16[{GMM_LAYERS},{E},{K},{N}]" in text
    # the stack is read in place: nothing as large as a layer's bank is made
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * K * N


# ---------------------------------------------------------------------------
# the serving programs at the benchmark's size: a layer's pool is addressed
# inside the arena, never copied out of it
# ---------------------------------------------------------------------------

# opt-1.3b as benchmarks/ serves it, cut to 3 layers: arena
# bf16[3, 2957, 16, 2048], one layer's pool 185 MiB
LAYERS, NUM_BLOCKS, BLOCK, ROWS, MAXB, CHUNK = 3, 2957, 16, 16, 128, 256
SPEC_TOKENS = 5         # the pending token and the default 4 draft slots
POOL_BYTES = NUM_BLOCKS * BLOCK * 2048 * 2
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$")
_RESULT = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(")


def _serving_program(kind, v5e, monkeypatch, preset="opt-1.3b",
                     overrides=None, rows=ROWS, num_blocks=NUM_BLOCKS,
                     chunk=CHUNK, **program_options):
    """One of ``paged_kv``'s programs (decode, prefill, mixed, verify, score)
    lowered for the described chip on its kernel path
    (``jax.default_backend()`` is the CPU here, so the platform probe is
    steered)."""
    from deepspeed_tpu.inference.kv_cache import paged_cache_shape_struct
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.presets import transformer_config
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.serving import paged_kv

    monkeypatch.setattr(registry, "kernels_active", lambda: True)
    cfg = transformer_config(preset, dtype=BF16,
                             **(overrides or {"num_layers": LAYERS}))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    from deepspeed_tpu.inference.kv_cache import ring_blocks

    params = on_chip(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    # a row owns a slot for a recurrent state, a window's ring, or both
    recurrent = bool(T.recurrent_layers(cfg)[1] or T.ring_layers(cfg))
    maxb = program_options.pop("maxb", MAXB)
    arena = on_chip(paged_cache_shape_struct(
        cfg, num_blocks, BLOCK, BF16,
        state_slots=rows + 1 if recurrent else 0,
        ring_blocks=ring_blocks(cfg, chunk, BLOCK)))

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e)

    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    r = rows
    if kind == "decode":
        # as the serving engine calls it: its last result, on the device,
        # behind the key (an MoE model's counts lie behind the tokens)
        last = arg((r + T.moe_count_width(cfg)
                    * bool(program_options.get("moe_counts")),), I32)
        return paged_kv.build_decode_program(cfg, **program_options).lower(
            params, arena, arg(paged_kv.decode_rows_shape(r, maxb), I32), key,
            last)
    if kind == "mixed":
        return paged_kv.build_mixed_program(cfg, chunk).lower(
            params, arena, arg(paged_kv.decode_rows_shape(r, maxb), I32),
            arg(paged_kv.chunk_shape(maxb, chunk, False), I32), key,
            arg((r,), I32))
    if kind == "verify":
        return paged_kv.build_verify_program(cfg, SPEC_TOKENS).lower(
            params, arena,
            arg(paged_kv.verify_rows_shape(r, maxb, SPEC_TOKENS), I32), key)
    if kind == "score":
        return paged_kv.build_score_program(cfg).lower(
            params, arena, arg((1, maxb), I32), arg((1, chunk), I32),
            arg((1, chunk), I32), arg((), I32), arg((), I32))
    return paged_kv.build_prefill_program(
        cfg, chunk, **program_options).lower(
            params, arena,
            arg(paged_kv.chunk_shape(maxb, chunk, recurrent,
                                     T.tail_runs(cfg) > 0), I32), key)


def _custom_calls(text, kernel):
    """How many Mosaic calls of ``kernel`` a compiled program's text holds."""
    return sum(kernel in ln for ln in text.splitlines()
               if "custom-call" in ln and "tpu_custom_call" in ln)


def _fusion_roots(text):
    """The op at the root of each computation of an optimised HLO module."""
    roots, current = {}, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = head.group(1)
        m = _RESULT.match(line)
        if m and line.lstrip().startswith("ROOT"):
            roots[current] = m.group(3)
    return roots


def _writes_in_place(line, op, roots):
    """An op that hands the arena along or scatters into it where it lies."""
    if op in ("parameter", "get-tuple-element", "tuple", "while", "scatter"):
        return True
    return op == "fusion" and roots.get(
        re.search(r"calls=%([\w.\-]+)", line).group(1)) == "scatter"


# the decode program's temporaries at the parent of PR 29, whose kernel kept
# one 16-token page a side in VMEM: the walk's tiles are VMEM too, not HBM
# (4,268,032 and 4,364,800), and what the sampler's conditional keeps beside
# them since PR 39 (63 and 126 KB: the sampler alone compiles to as much
# more than its two-sort form). A copy of a pool would be gigabytes.
PARENT_DECODE_TEMP_BYTES = {"opt-1.3b": 4_332_544, "olmoe-1b-7b": 4_493_824}


@pytest.mark.parametrize("preset", sorted(PARENT_DECODE_TEMP_BYTES))
def test_decode_walk_reads_the_arena_where_it_lies(v5e, monkeypatch, preset):
    """`jit_decode` at the cells' widths with the kernel that walks the
    pages itself: its k and v operands (left in HBM, `pl.ANY`) are the arena
    as the layer's scatter wrote it, not a copy of it, and the program's
    temporaries are no larger than the parent's."""
    options = {"moe_counts": True} if preset.startswith("olmoe") else {}
    compiled = _serving_program("decode", v5e, monkeypatch, preset=preset,
                                **options).compile()
    text = compiled.as_text()
    roots = _fusion_roots(text)
    made_by = {}
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m:
            made_by[m.group(1)] = (line, m.group(3))
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "paged_decode_attention" in ln]
    assert len(calls) == 1
    operands = re.findall(r"%([\w.\-]+)",
                          calls[0].split("custom-call(", 1)[1].split(")")[0])
    for name in operands[4:6]:                 # after 3 scalars and q
        line, op = made_by[name]
        assert f"bf16[{LAYERS},{NUM_BLOCKS},{BLOCK},2048]" in line
        assert _writes_in_place(line, op, roots), line.strip()[:200]
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= PARENT_DECODE_TEMP_BYTES[preset])


@pytest.mark.parametrize("kind", ["decode", "prefill", "verify", "score"])
def test_serving_program_never_copies_a_pool(v5e, monkeypatch, kind):
    """No CPU test can see a pool copy: the numbers are the same with it.
    The optimised HLO for the chip shows it, and so does the temporary
    memory (three pools at the parent of PR 26, a few MiB since). Every
    program with more than one query a row (a prompt chunk, a speculative
    verify step, an RLHF scoring chunk) reads through the prefill kernel."""
    compiled = _serving_program(kind, v5e, monkeypatch).compile()
    pool = f"bf16[{NUM_BLOCKS},{BLOCK},2048]"
    arena = f"bf16[{LAYERS},{NUM_BLOCKS},{BLOCK},2048]"
    kernel = ("paged_decode_attention" if kind == "decode"
              else "paged_prefill_attention")
    text = compiled.as_text()
    roots = _fusion_roots(text)
    calls, offenders = 0, []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m is None:
            continue
        _, result, op = m.groups()
        if op == "custom-call" and kernel in line:
            calls += 1
            # the kernel's k and v operands are the arena, not a pool
            operands = line.split("operand_layout_constraints=", 1)[1]
            assert operands.count(arena) == 2 and pool not in operands, line
        if pool not in result and arena not in result:
            continue
        # what may have the arena's shape: the program's and the loop's
        # operands and results handed along, and the in-place scatter
        if _writes_in_place(line, op, roots):
            continue
        offenders.append(line.strip()[:200])
    assert not offenders, "\n".join(offenders)
    assert calls >= 1, f"no custom call named {kernel}"
    assert compiled.memory_analysis().temp_size_in_bytes < POOL_BYTES


# what one scatter into the arena places, by program: a token's row for each
# of the step's rows (decode), for each row's draft slots (verify), and for a
# run of CHUNK positions the whole pages it can touch (PR 49: a row scatter
# there has CHUNK index rows, each a half-word write on the chip's tiling)
ARENA_UPDATES = {
    "decode": f"bf16[{ROWS},2048]",
    "verify": f"bf16[{ROWS},{SPEC_TOKENS},2048]",
    "prefill": f"bf16[{CHUNK // BLOCK + 1},{BLOCK},2048]",
    "score": f"bf16[{CHUNK // BLOCK + 1},{BLOCK},2048]",
}


@pytest.mark.parametrize("kind", sorted(ARENA_UPDATES))
def test_a_run_is_written_in_pages_and_a_token_in_a_row(v5e, monkeypatch,
                                                        kind):
    """The two writes of a layer (k, v) are one scatter each into the arena
    where it lies; a chunk's is of whole pages, never of CHUNK rows, and the
    decode and verify programs' are the row scatters they were."""
    compiled = _serving_program(kind, v5e, monkeypatch).compile()
    arena = f"bf16[{LAYERS},{NUM_BLOCKS},{BLOCK},2048]"
    shape_of, updates = {}, []
    for line in compiled.as_text().splitlines():
        m = _RESULT.match(line)
        if m is None:
            continue
        name, result, op = m.groups()
        shape_of[name] = result.split("{")[0]
        if op == "scatter" and result.startswith(arena):
            # (the arena, the index rows, the updates), each defined above
            updates.append(shape_of[re.findall(
                r"%([\w.\-]+)", line.split(" scatter(", 1)[1])[2]])
    assert updates == [ARENA_UPDATES[kind]] * 2
    memory = compiled.memory_analysis()
    # both sides of the arena are the program's own operand, written in place
    assert memory.alias_size_in_bytes >= 2 * LAYERS * POOL_BYTES
    if kind in ("prefill", "score"):
        # the pages gathered and laid over are 17 x 64 KB a side (parent of
        # PR 49: 902,144 and 451,584 bytes of temporaries; 1,031,168 and
        # 612,864 with them)
        assert memory.temp_size_in_bytes < 2 << 20


@pytest.mark.parametrize("kind,rows", [("decode", ROWS), ("prefill", CHUNK)])
def test_olmoe_serving_program_computes_assigned_rows_only(v5e, monkeypatch,
                                                           kind, rows):
    """OLMoE-1B-7B's two serving programs (3 layers) for the chip: the
    expert compute is the kernel `moe_grouped_matmul` (gate with up, down), no
    tensor has the (experts, rows, hidden) shape of the capacity path's
    dispatch with C = T, the paged kernel is there at head size 128 and no
    pool-sized temporary is."""
    compiled = _serving_program(kind, v5e, monkeypatch, preset="olmoe-1b-7b",
                                moe_counts=True).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "custom-call" in ln
             and "tpu_custom_call" in ln]
    assert sum("moe_grouped_matmul" in ln for ln in calls) == 2
    assert sum(f"paged_{kind}_attention" in ln for ln in calls) == 1
    for width in (2048, 1024):
        assert f"[64,{rows},{width}]" not in text
    # nor is a layer's bank of experts copied out of the stack for the kernel
    assert "bf16[64,2048,1024]" not in text
    assert "bf16[64,1024,2048]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < POOL_BYTES


# solar-open2-250b as the benchmark serves it: one period of four layers, 40
# of 320 experts held, an eighth of the vocabulary, 64 rows, 8,192 blocks
SOLAR = {"num_layers": 4, "moe_experts_held": 40, "vocab_size": 24576}
SOLAR_ROWS, SOLAR_BLOCKS = 64, 8193
SOLAR_STATES = f"f32[3,{SOLAR_ROWS + 1},64,128,128]"


@pytest.mark.parametrize("kind", ["decode", "prefill", "score",
                                  "score-step"])
def test_solar_serving_program_updates_the_states_where_they_lie(
        v5e, monkeypatch, kind):
    """Solar Open 2's serving programs for the chip at the cell's shapes: a
    decode step is three calls of `kda_decode_step` (one a linear-attention
    layer) whose pool operand is the whole pool and comes back aliased; a
    chunk program writes a row's state back by an in-place update; no
    program holds a second copy of the pool (818 MB) or of the pages, and the
    softmax layer reads its ONE layer of pages through the paged kernel.
    The score program at a width of one (`score_logprobs`' last tokens) is
    the decode step's kernels on one row."""
    steps = kind in ("decode", "score-step")
    compiled = _serving_program(
        kind.split("-")[0], v5e, monkeypatch, preset="solar-open2-250b",
        overrides=SOLAR, rows=SOLAR_ROWS, num_blocks=SOLAR_BLOCKS,
        **({"chunk": 1} if kind == "score-step" else {}),
        **({} if "score" in kind else {"moe_counts": True})).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "custom-call" in ln
             and "tpu_custom_call" in ln]
    assert sum("kda_decode_step" in ln for ln in calls) \
        == (3 if steps else 0)
    assert sum("moe_grouped_matmul" in ln for ln in calls) == 2 * 4
    paged = "paged_decode_attention" if steps \
        else "paged_prefill_attention"
    assert sum(paged in ln for ln in calls) == 1
    pages = f"bf16[1,{SOLAR_BLOCKS},{BLOCK},1024]"
    for ln in calls:
        if "kda_decode_step" in ln:
            assert SOLAR_STATES in ln.split("custom-call(", 1)[0]   # a result
        if paged in ln:
            assert ln.split("operand_layout_constraints=", 1)[1].count(
                pages) == 2
    # a layer's experts are read where they lie: no (40, 4096, 1280) copy
    assert "bf16[40,4096,1280]" not in text
    assert "bf16[40,1280,4096]" not in text
    pool_bytes = 3 * (SOLAR_ROWS + 1) * 64 * 128 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= pool_bytes        # donated, in place


# nemotron-3-super as the benchmark serves it: the first 11 source layers (5
# Mamba-2, 5 expert layers, 1 attention), 64 of 512 experts held, an eighth
# of the vocabulary, 64 rows, 8,192 blocks
NEMOTRON = {"num_layers": 11, "moe_experts_held": 64, "vocab_size": 16384}
NEMOTRON_STATES = f"f32[5,{SOLAR_ROWS + 1},8,128,1024]"


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_nemotron_serving_program_updates_the_states_where_they_lie(
        v5e, monkeypatch, kind):
    """Nemotron 3 Super's serving programs for the chip at the cell's
    shapes: a decode step is five calls of `mamba2_decode_step` (one a
    Mamba-2 layer) whose pool operand is the whole pool and comes back
    aliased; the chunk program reads and writes a row's state in the pool's
    own layout, so that no program holds a second copy of the pool (1.36 GB;
    a transpose between the pool and the chunked form's arithmetic is folded
    into the pool's layout and copies it in and out) or of a layer's experts,
    and the attention layer reads its ONE layer of pages through the paged
    kernel."""
    compiled = _serving_program(
        kind, v5e, monkeypatch, preset="nemotron-3-super-120b-a12b",
        overrides=NEMOTRON, rows=SOLAR_ROWS, num_blocks=SOLAR_BLOCKS,
        moe_counts=True).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "custom-call" in ln
             and "tpu_custom_call" in ln]
    steps = kind == "decode"
    assert sum("mamba2_decode_step" in ln for ln in calls) \
        == (5 if steps else 0)
    assert sum("moe_grouped_matmul" in ln for ln in calls) == 5 * 2
    paged = "paged_decode_attention" if steps \
        else "paged_prefill_attention"
    assert sum(paged in ln for ln in calls) == 1
    for ln in calls:
        if "mamba2_decode_step" in ln:
            assert NEMOTRON_STATES in ln.split("custom-call(", 1)[0]
    if steps:
        # which of the ten expert matmuls' results the compiler keeps in
        # fast memory (`S(1)` on the result's layout; ROADMAP A19 (1)),
        # as read at PR 68: all five down calls' (3,456 x 1,024) and one
        # up call's (3,456 x 2,688) under whole-matrix blocks, where the
        # column blocks' calls had the five down results alone
        fast = [ln.split("custom-call(", 1)[0] for ln in calls
                if "moe_grouped_matmul" in ln
                and "S(1)" in ln.split("custom-call(", 1)[0]]
        assert sum("bf16[3456,1024]" in result for result in fast) == 5
        assert len(fast) >= 5
    # a layer's experts are read where they lie: no (64, 1024, 2688) copy
    assert "bf16[64,1024,2688]" not in text
    assert "bf16[64,2688,1024]" not in text
    pool_bytes = 5 * (SOLAR_ROWS + 1) * 8 * 128 * 1024 * 4
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < pool_bytes // 4
    assert m.alias_size_in_bytes >= pool_bytes        # donated, in place


# ---------------------------------------------------------------------------
# a decode step's attention projections read their weights where they lie
# ---------------------------------------------------------------------------

# preset: (how `_serving_program` builds its decode program; hidden, N*D and
# K*D; the layers in the attention weights' stack; the weights a layer: q,
# k, v, o and Solar's output gate)
DECODE_PROJECTIONS = {
    "opt-1.3b": ({}, (2048, 2048, 2048), LAYERS, 4),
    "olmoe-1b-7b": ({"moe_counts": True}, (2048, 2048, 2048), LAYERS, 4),
    "solar-open2-250b": (
        dict(overrides=SOLAR, rows=SOLAR_ROWS, num_blocks=SOLAR_BLOCKS,
             moe_counts=True), (4096, 8192, 1024), 1, 5),
    "nemotron-3-super-120b-a12b": (
        dict(overrides=NEMOTRON, rows=SOLAR_ROWS, num_blocks=SOLAR_BLOCKS,
             moe_counts=True), (4096, 4096, 256), 1, 4),
}


# phi-4-mini-flash-reasoning as the benchmark serves it: all 32 layers, 64 rows
# of up to 2,560 tokens; pages for the ONE full layer, a ring of 48 pages a
# row and window layer, a Mamba-1 state a row and layer
PHI_ROWS, PHI_BLOCKS, PHI_MAXB = 64, 10241, 160
PHI_STATES = f"f32[9,{PHI_ROWS + 1},16,5120]"
PHI_RINGS = f"bf16[8,{1 + (PHI_ROWS + 1) * 48},{BLOCK},1280]"


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_phi4flash_serving_program_scans_its_runs_and_copies_no_pool(
        v5e, monkeypatch, kind):
    """Phi-4-mini-flash-reasoning's serving programs for the chip at the
    cell's shapes. The stack's three runs are scanned, so a program holds ONE
    call of each kernel a run, not one a layer: a decode step has two calls
    of `mamba1_decode_step` (the 8 periods' and layer 16's), one windowed
    walk and two walks of the shared pool (layer 17's and the cross
    layers'); the chunk program runs the cross-decoder for its last token
    alone, a decode walk. The state pool and the rings come back aliased,
    and no program holds a second copy of a pool, of the rings (2 GB) or of
    a run's weights."""
    compiled = _serving_program(
        kind, v5e, monkeypatch, preset="phi-4-mini-flash-reasoning",
        overrides={"num_layers": 32}, rows=PHI_ROWS, num_blocks=PHI_BLOCKS,
        maxb=PHI_MAXB).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "custom-call" in ln
             and "tpu_custom_call" in ln]

    def count(kernel):
        return sum(re.search(rf"%?{kernel}(\.\d+)? = ", ln) is not None
                   for ln in calls)

    steps = kind == "decode"
    assert count("mamba1_decode_step") == (2 if steps else 0)
    assert count("mamba1_chunk_scan") == (0 if steps else 2)
    assert count("window_decode_attention") == (1 if steps else 0)
    assert count("shared_kv_decode_attention") \
        == {"decode": 2, "prefill": 1}[kind]
    assert count("paged_prefill_attention") \
        == {"decode": 0, "prefill": 2}[kind]
    assert PHI_STATES in text and PHI_RINGS in text
    # nothing pool-sized is a temporary: the full pool is 0.42 GB a side,
    # the rings 1.0 GB a side, the states 0.21 GB, a run's FFN weights 1.3 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 300e6
    for shape in (PHI_STATES, PHI_RINGS,
                  f"bf16[1,{PHI_BLOCKS},{BLOCK},1280]"):
        copied = [ln for ln in text.splitlines()
                  if re.search(rf"= {re.escape(shape)}\S* copy\(", ln)]
        assert not copied, copied[:2]


# ouro-2.6b as its cell serves it, whole: 4 passes x 48 layers, 16 rows of
# 320 tokens and the scratch block; the arena 4.04 GB a side, a pool 21 MB
OURO_ROWS, OURO_BLOCKS, OURO_MAXB, OURO_CHUNK = 16, 321, 20, 128
OURO_ARENA = f"bf16[192,{OURO_BLOCKS},{BLOCK},2048]"


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_a_looped_stack_carries_its_arena_through_both_loops(
        v5e, monkeypatch, kind):
    """Ouro-2.6B's serving programs for the chip at the cell's shapes: the
    passes and the layers are two nested loops, so a program holds ONE walk
    and one body of a layer, not 192; the arena rides the carry of both and
    is scattered into where it lies (a copy of it, 4 GB a side, would not
    fit beside the weights: that is how it would show on the chip); the
    weights are the inner loop's operand, and no stack of 48 is re-laid at
    the program's entry (the compiler did that to wk, and to wq in the
    chunk, until k's product was parted from its rope: 0.4 GB a copy)."""
    compiled = _serving_program(
        kind, v5e, monkeypatch, preset="ouro-2.6b",
        overrides={"num_layers": 48}, rows=OURO_ROWS,
        num_blocks=OURO_BLOCKS, maxb=OURO_MAXB, chunk=OURO_CHUNK).compile()
    text = compiled.as_text()
    roots = _fusion_roots(text)
    kernel = ("paged_decode_attention" if kind == "decode"
              else "paged_prefill_attention")
    calls, offenders, loops = 0, [], 0
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m is None:
            continue
        _, result, op = m.groups()
        loops += op == "while" and OURO_ARENA in result
        if op == "custom-call" and kernel in line:
            calls += 1
            operands = line.split("operand_layout_constraints=", 1)[1]
            assert operands.count(OURO_ARENA) == 2, line[:300]
        if OURO_ARENA in result and not _writes_in_place(line, op, roots):
            offenders.append(line.strip()[:200])
        if op == "copy" and re.match(r"bf16\[48,", result):
            offenders.append(line.strip()[:200])
    assert not offenders, "\n".join(offenders)
    assert calls == 1 and loops == 2, (calls, loops)
    # a layer's FFN weights are 69 MB, a pool 21 MB, a stack of wk 0.4 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6


LONGCAT_ROWS, LONGCAT_BLOCKS, LONGCAT_MAXB, LONGCAT_CHUNK = 32, 10241, 320, \
    1024
LONGCAT_ARENA = f"bf16[8,{LONGCAT_BLOCKS},{BLOCK},640]"
# the ONE double-layer body: gate with up, down
LONGCAT_GMM = 2


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_a_double_layer_reads_its_sublayers_where_they_lie(
        v5e, monkeypatch, kind):
    """LongCat-Flash-Chat's serving programs for the chip at the cell's
    shapes (4 double layers, 16 of 512 routed experts held, 32 rows over an
    arena of 10,241 blocks of 640-lane latent pages): the latent arena rides
    the layer loop's carry and is scattered into where it lies; the decode
    program's body holds TWO walks, one a sublayer, each handed the arena
    ONCE (keys and values are the same pages, copied once); no sublayer's
    weights are copied out of their stack
    (sliced a layer by the scan, the compiler copied a layer's two sublayers
    out before each read its half: 1.3 GB a layer, 308 MB of temporaries);
    the chunk program reads expanded and calls no walk."""
    compiled = _serving_program(
        kind, v5e, monkeypatch, preset="longcat-flash-chat",
        overrides={"num_layers": 4, "moe_experts_held": 16,
                   "vocab_size": 16384}, rows=LONGCAT_ROWS,
        num_blocks=LONGCAT_BLOCKS, maxb=LONGCAT_MAXB, chunk=LONGCAT_CHUNK,
        moe_counts=True).compile()
    text = compiled.as_text()
    roots = _fusion_roots(text)
    calls, offenders = 0, []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m is None:
            continue
        _, result, op = m.groups()
        if op == "custom-call" and "latent_decode_attention" in line:
            calls += 1
            operands = line.split("operand_layout_constraints=", 1)[1]
            assert operands.count(LONGCAT_ARENA) == 1, line[:300]
        if LONGCAT_ARENA in result and not _writes_in_place(line, op, roots):
            offenders.append(line.strip()[:200])
        # a layer's or a sublayer's dense FFN, attention or expert matrices
        # (a chunk's 1,024 x 12 assigned rows of 6,144 are an activation)
        if op in ("copy", "transpose") and "/gather" not in line and re.match(
                r"bf16\[(4,)?(2,)?(6144,12288|12288,6144|8192,6144|"
                r"1536,12288|16,6144,2048|16,2048,6144)\]", result):
            offenders.append(line.strip()[:200])
    assert not offenders, "\n".join(offenders)
    assert calls == (2 if kind == "decode" else 0)
    assert _custom_calls(text, "moe_grouped_matmul") == LONGCAT_GMM
    # a sublayer's dense FFN matrix is 151 MB; the chunk's scores, 8 heads
    # at a time in float32, and its experts' rows are 0.4 GB
    assert compiled.memory_analysis().temp_size_in_bytes < (
        16e6 if kind == "decode" else 0.5e9)


LFM2_ROWS, LFM2_BLOCKS, LFM2_MAXB, LFM2_CHUNK = 16, 8321, 520, 1024
# the six layer bodies with experts: gate with up, down
LFM2_GMM = 2 * 6
LFM2_ARENA = f"bf16[3,{LFM2_BLOCKS},{BLOCK},512]"
LFM2_TAILS = f"bf16[9,{LFM2_ROWS + 1},2,2048]"


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_a_tail_only_stack_reads_banks_and_pools_where_they_lie(
        v5e, monkeypatch, kind):
    """LFM2-8B-A1B's serving programs for the chip at its cell's shapes (the
    first 12 layers in three runs, 16 rows over an arena of 8,321 blocks,
    chunks of 1,024): the pages of the 3 attention layers and the tails of
    the 9 convolution layers ride the runs' carries, the pages written
    where they lie (the tails are 1.25 MB: a slot's two rows are updated in
    them); no layer's experts are copied out of their kind's bank (352 MB
    a layer) and no leading layer's dense FFN out of its stack; both paged
    kernels and the grouped matmul are in the program, and there is no
    ``"state"`` pool at all."""
    compiled = _serving_program(
        kind, v5e, monkeypatch, preset="lfm2-8b-a1b",
        overrides={"num_layers": 12}, rows=LFM2_ROWS, num_blocks=LFM2_BLOCKS,
        maxb=LFM2_MAXB, chunk=LFM2_CHUNK, moe_counts=True).compile()
    text = compiled.as_text()
    roots = _fusion_roots(text)
    offenders = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m is None:
            continue
        _, result, op = m.groups()
        if LFM2_ARENA in result and not _writes_in_place(line, op, roots):
            offenders.append(line.strip()[:200])
        # a layer's experts or a kind's bank, a dense FFN's matrices (a
        # chunk's 4,096 assigned rows are an activation)
        if op in ("copy", "transpose") and "/gather" not in line and re.match(
                r"bf16\[([237],)?(32,)?(2048,1792|1792,2048|2048,7168|"
                r"7168,2048|2048,6144)\]", result):
            offenders.append(line.strip()[:200])
    assert not offenders, "\n".join(offenders)
    assert LFM2_ARENA in text and LFM2_TAILS in text
    assert _custom_calls(text, "moe_grouped_matmul") == LFM2_GMM
    assert ("paged_decode_attention" if kind == "decode"
            else "paged_prefill_attention") in text
    # a chunk's experts' rows, 4,096 x 1,792 twice over, and its scores
    assert compiled.memory_analysis().temp_size_in_bytes < (
        16e6 if kind == "decode" else 0.5e9)


# mimo-v2-flash as its cell serves it: layer 0 and one whole period (5 window
# layers, a full one), 16 of 256 experts held, an eighth of the vocabulary;
# 32 rows of up to 10,240 tokens: pages for the 2 full layers, a ring of 72
# pages a slot for the 5 window layers, and no state at all
MIMO = {"num_layers": 7, "moe_experts_held": 16, "vocab_size": 19072}
MIMO_ROWS, MIMO_BLOCKS, MIMO_MAXB, MIMO_CHUNK = 32, 20481, 640, 1024
MIMO_PAGES = (f"bf16[2,{MIMO_BLOCKS},{BLOCK},768]",
              f"bf16[2,{MIMO_BLOCKS},{BLOCK},512]")
MIMO_RINGS = (f"bf16[5,{1 + (MIMO_ROWS + 1) * 72},{BLOCK},1536]",
              f"bf16[5,{1 + (MIMO_ROWS + 1) * 72},{BLOCK},1024]")


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_rings_without_a_state_ride_the_runs_carry_where_they_lie(
        v5e, monkeypatch, kind):
    """MiMo-V2-Flash's serving programs for the chip at its cell's shapes: a
    window layer's ring is addressed by the row's slot with no recurrent
    layer in the model (the arena holds `"slots"`, 33 int32, and no
    `"tail"`); keys and values are pools of different widths in both forms;
    a decode step walks the 5 rings under `window_decode_attention` and the
    2 pools under `full_kv_decode_attention`, a chunk goes through the
    prefill kernel 7 times; neither the pages (1.68 GB) nor the rings (0.97
    GB) are copied, and no layer's experts leave their bank."""
    compiled = _serving_program(
        kind, v5e, monkeypatch, preset="mimo-v2-flash", overrides=MIMO,
        rows=MIMO_ROWS, num_blocks=MIMO_BLOCKS, maxb=MIMO_MAXB,
        chunk=MIMO_CHUNK, moe_counts=True).compile()
    text = compiled.as_text()
    roots = _fusion_roots(text)
    offenders = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m is None:
            continue
        _, result, op = m.groups()
        if any(pool in result for pool in MIMO_PAGES + MIMO_RINGS) \
                and not _writes_in_place(line, op, roots):
            offenders.append(line.strip()[:200])
        # a layer's experts or a kind's bank, the dense FFN's matrices
        if op in ("copy", "transpose") and "/gather" not in line and re.match(
                r"bf16\[([15],)?(16,)?(4096,2048|2048,4096|4096,16384|"
                r"16384,4096|4096,12288|8192,4096)\]", result):
            offenders.append(line.strip()[:200])
    assert not offenders, "\n".join(offenders)
    for pool in MIMO_PAGES + MIMO_RINGS:
        assert pool in text, pool
    assert "s32[33]" in text
    steps = kind == "decode"
    assert _custom_calls(text, "window_decode_attention") == (5 if steps else 0)
    assert _custom_calls(text, "full_kv_decode_attention") \
        == (2 if steps else 0)
    assert _custom_calls(text, "paged_prefill_attention") \
        == (0 if steps else 7)
    assert "shared_kv_decode_attention" not in text
    assert _custom_calls(text, "moe_grouped_matmul") == 2 * 6
    assert compiled.memory_analysis().temp_size_in_bytes < (
        32e6 if steps else 1.0e9)


def _projection_weights(text, widths, layers):
    """(the instructions outside a fused computation that MAKE a whole
    attention projection weight: a copy, a transpose, or a fusion with no
    product in it, such as a slice out of the layer stack; the fusions
    outside one that hold a product and take an attention weight's stack
    itself as an operand, prefetched or not)."""
    hidden, heads, kv = widths
    pairs = {(hidden, heads), (heads, hidden), (hidden, kv), (kv, hidden)}
    whole = {f"bf16[{lead}{a},{b}]" for a, b in pairs for lead in ("", "1,")}
    stacks = {f"bf16[{layers},{a},{b}]" for a, b in pairs}
    sources = {(kind, stack) for stack in stacks
               for kind in ("parameter", "get-tuple-element")}
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    body, made_by, outside, current = {}, {}, [], None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = head.group(1)
            continue
        body.setdefault(current, []).append(line)
        m = _RESULT.match(line)
        if m is None:
            continue
        name, result, op = m.groups()
        operands = re.findall(r"%([\w.\-]+)",
                              line.split(f" {op}(", 1)[1].split(")")[0])
        made_by[name] = (op, result.split("{")[0], operands)
        if current not in fused:
            outside.append((name, line))

    def source(name):
        """What an operand is, behind the bitcasts and the asynchronous
        prefetch that leave its bytes as they are."""
        op, result, operands = made_by[name]
        while op in ("bitcast", "copy-done", "copy-start"):
            op, result, operands = made_by[operands[0]]
        return op, result

    makers, readers = [], []
    for name, line in outside:
        op, result, operands = made_by[name]
        product = op == "fusion" and any(
            " convolution(" in ln for ln in
            body[re.search(r"calls=%([\w.\-]+)", line).group(1)])
        if result in whole and not product and op in ("copy", "transpose",
                                                      "fusion"):
            makers.append(line.strip()[:160])
        if product and any(source(o) in sources for o in operands):
            readers.append(name)
    return makers, readers


@pytest.mark.parametrize("preset", sorted(DECODE_PROJECTIONS))
def test_a_decode_step_reads_its_attention_weights_where_they_lie(
        v5e, monkeypatch, preset):
    """No CPU test can see a weight copied: the numbers are the same. With
    the reshape to heads next to q's product the chip's compiler made the
    product heads-major and re-laid the WEIGHT for it every step (opt-1.3b:
    a slice of `[1,2048,2048]` out of the stack and a transposing copy a
    layer; Solar and Nemotron: a copy of the whole `[8192,4096]` /
    `[4096,4096]` at the program's entry; PR 53). Every attention
    projection of a decode step, q's as k's and v's, is ONE fusion that
    holds the product and reads the layer stack itself."""
    options, widths, layers, weights = DECODE_PROJECTIONS[preset]
    text = _serving_program("decode", v5e, monkeypatch, preset=preset,
                            **options).compile().as_text()
    makers, readers = _projection_weights(text, widths, layers)
    assert not makers, "\n".join(makers)
    assert len(readers) == weights, readers


def test_the_mixed_step_reads_each_weight_once_and_copies_none(v5e,
                                                               monkeypatch):
    """`jit_mixed_step` at opt-1.3b's widths (3 layers, 16 rows beside a
    chunk of 256): ONE pass over the layers, so each attention projection is
    one product over the 272 tokens that reads the layer stack where it
    lies (with the run sliced into its two parts next to the products, the
    compiler sliced wq, wk and wv out of the stack and transposed them, a
    layer: PR 61), and the FFN's two products and the tied head appear once;
    both paged kernels are there, a call each, on the whole arena, which
    the two writes of each part (pages for the chunk, rows for the rows)
    update in place; and the temporaries are the decode program's walk
    tiles, not a pool."""
    compiled = _serving_program("mixed", v5e, monkeypatch).compile()
    text = compiled.as_text()
    makers, readers = _projection_weights(text, (2048, 2048, 2048), LAYERS)
    assert not makers, "\n".join(makers)
    assert len(readers) == 4, readers
    pool = f"bf16[{NUM_BLOCKS},{BLOCK},2048]"
    arena = f"bf16[{LAYERS},{NUM_BLOCKS},{BLOCK},2048]"
    roots = _fusion_roots(text)
    calls, offenders, updates, shape_of = [], [], [], {}
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m is None:
            continue
        name, result, op = m.groups()
        shape_of[name] = result.split("{")[0]
        if op == "custom-call" and "tpu_custom_call" in line:
            calls.append(line)
            if "paged_" in line:
                operands = line.split("operand_layout_constraints=", 1)[1]
                assert operands.count(arena) == 2 and pool not in operands
        if op == "scatter" and result.startswith(arena):
            updates.append(shape_of[re.findall(
                r"%([\w.\-]+)", line.split(" scatter(", 1)[1])[2]])
        if (pool in result or arena in result) \
                and not _writes_in_place(line, op, roots):
            offenders.append(line.strip()[:200])
    assert not offenders, "\n".join(offenders)
    assert sum("paged_prefill_attention" in ln for ln in calls) == 1
    assert sum("paged_decode_attention" in ln for ln in calls) == 1
    # the chunk's k and v as whole pages, then the rows' as a row a token
    assert updates == [ARENA_UPDATES["prefill"]] * 2 \
        + [ARENA_UPDATES["decode"]] * 2
    # the FFN's weights are read by one product each, over all 272 tokens
    assert "bf16[272,8192]" in text
    assert "bf16[256,8192]" not in text and "bf16[16,8192]" not in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * LAYERS * POOL_BYTES
    assert memory.temp_size_in_bytes <= PARENT_DECODE_TEMP_BYTES[
        "olmoe-1b-7b"]


# ---------------------------------------------------------------------------
# the platform probe those programs are steered by: one name, one owner
# ---------------------------------------------------------------------------

def test_the_platform_probe_has_one_owner_and_one_name():
    """``ops.registry.kernels_active`` is defined in one module and every
    reader calls it through that module. A wrapper or a ``from`` import is a
    second steerable name: rebinding one then steers some kernels and leaves
    the rest on their references, and the compiled program is neither the
    chip's nor the CPU's."""
    package = REPO / "deepspeed_tpu"
    owner = package / "ops" / "registry.py"
    assert "def kernels_active()" in owner.read_text()
    for path in package.rglob("*.py"):
        if path == owner:
            continue
        for before, call in re.findall(r"(\S*?)kernels_active(\(\))?",
                                       path.read_text()):
            assert before.endswith("registry.") and call, \
                f"{path.relative_to(REPO)}: {before}kernels_active{call}"


def test_the_rehearsal_steers_the_probe_the_programs_read():
    """``benchmarks/rehearse.py`` compiles the cells' programs for the
    described chip as ``_serving_program`` does here, and must steer the
    same name: a probe that moves again fails here, not in a rehearsal
    that passes on the reference path."""
    text = (REPO / "benchmarks" / "rehearse.py").read_text()
    steered = re.findall(r'patch\.object\((\w+), "(\w*kernels_active)"', text)
    assert steered == [("registry", "kernels_active")]
    assert not re.findall(r"^\s*\w+\.\w*kernels_active = ", text, re.M)
