"""The kernels of the main paths, compiled by the TPU's own compiler at real widths.

Interpret mode cannot see what the chip's compiler refuses: a block shape whose
last two dimensions are neither tile-aligned nor whole, a kernel that needs more
fast memory than it may take. These tests compile each kernel for a v5e that is
described and not attached (``on-chip-measurement`` guide, section 2), about two
seconds each. Nothing runs, so they say nothing about results or times:
``chip_smoke.py`` does that on the chip.

Everything that touches the TPU's library is built inside the module-scoped
fixtures below: only the pytest worker that is given this file loads it, and it
keeps it, so the tests compile in their own process and live in this one file.
"""

import functools
import re

import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.flash_attention import flash_attention
from accelerate_tpu.ops.paged_decode import (
    fused_sample,
    paged_flash_decode,
    paged_flash_verify,
)

# (query heads, kv heads, head_dim, vocabulary) as published
WIDTHS = {
    "gpt2_large": (20, 20, 64, 50257),
    "mistral_7b": (32, 8, 128, 32000),
    "qwen2_7b": (28, 4, 128, 152064),
    "lfm2_8b_a1b": (32, 8, 64, 65536),
}
SLOTS, BLOCK_SIZE, BLOCKS_PER_ROW, POOL_BLOCKS, WINDOW = 8, 16, 128, 512, 5


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: the next one would warn and compile
    again. Off around these tests, and back as it was after them."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compile_for_chip(one_chip, no_persistent_cache):
    """``compile_for_chip(fn, (shape, dtype), ...)`` compiles ``fn`` for the
    described chip and returns the optimized program's text."""

    def compile_(fn, *operands):
        shapes = [
            jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in operands
        ]
        text = jax.jit(fn).lower(*shapes).compile().as_text()
        assert "tpu_custom_call" in text, "no Pallas kernel in the compiled program"
        return text

    return compile_


def _flash_loss(q, k, v, **kwargs):
    return jnp.sum(flash_attention(q, k, v, interpret=False, **kwargs).astype(jnp.float32))


@pytest.mark.parametrize("seq_len", [2048, 4096])
def test_flash_forward_and_backward_compile_at_mistral_heads(compile_for_chip, seq_len):
    heads, kv_heads, head_dim, _ = WIDTHS["mistral_7b"]
    loss = functools.partial(
        _flash_loss, causal=True, window=4096, block_q=2048, block_k=512
    )
    q = ((1, seq_len, heads, head_dim), jnp.bfloat16)
    kv = ((1, seq_len, kv_heads, head_dim), jnp.bfloat16)
    compile_for_chip(loss, q, kv, kv)
    # the two backward kernels (dq; dk and dv) beside the forward's recompute
    text = compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") >= 3


def test_flash_packed_segments_compile_at_gpt2_head_dim(compile_for_chip):
    heads, kv_heads, head_dim, _ = WIDTHS["gpt2_large"]

    def loss(q, k, v, segments):
        return _flash_loss(q, k, v, causal=True, segment_ids=segments)

    qkv = ((2, 1024, heads, head_dim), jnp.bfloat16)
    compile_for_chip(
        jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv, ((2, 1024), jnp.int32)
    )


def _pool_operands(width, quantized):
    heads, kv_heads, head_dim, _ = WIDTHS[width]
    pool = (
        (POOL_BLOCKS, BLOCK_SIZE, kv_heads, head_dim),
        jnp.int8 if quantized else jnp.bfloat16,
    )
    tables = ((SLOTS, BLOCKS_PER_ROW), jnp.int32)
    pos = ((SLOTS,), jnp.int32)
    scales = [((POOL_BLOCKS, BLOCK_SIZE), jnp.float32)] * 2 if quantized else []
    return heads, kv_heads, head_dim, pool, tables, pos, scales


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16_pool", "int8_pool"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_paged_flash_decode_compiles(compile_for_chip, width, quantized):
    heads, _, head_dim, pool, tables, pos, scales = _pool_operands(width, quantized)

    def decode(q, k, v, tables, pos, *scales):
        kwargs = dict(zip(("k_scale", "v_scale"), scales))
        return paged_flash_decode(q, k, v, tables, pos, interpret=False, **kwargs)

    compile_for_chip(
        decode, ((SLOTS, 1, heads, head_dim), jnp.bfloat16), pool, pool, tables, pos, *scales
    )


# the engine's own call (PR 28): every layer's pool stacked, the two head axes
# merged, the layer a traced scalar that offsets the block tables. GPT-2 large
# as the serving cell runs it (32 slots of 1024 positions, 36 layers of 1025
# blocks: 3 GB of pool that nothing may copy), and Mistral's grouped heads.
WHOLE_POOLS = {
    "gpt2_large_serve_closed32": ("gpt2_large", 32, 64, 36, 1025),
    "mistral_7b_gqa": ("mistral_7b", 8, 128, 4, 512),
    # LFM2's serving cell: the pool spans its 3 attention layers of 13, rows of
    # 8 x 64 = 512 lanes, 32 slots of 1536 positions
    "lfm2_13l_serve_closed32": ("lfm2_8b_a1b", 32, 96, 3, 3073),
}


@pytest.mark.parametrize("case", list(WHOLE_POOLS))
def test_paged_flash_decode_compiles_on_the_whole_pool(compile_for_chip, case):
    width, slots, blocks_per_row, layers, pool_blocks = WHOLE_POOLS[case]
    heads, kv_heads, head_dim, _ = WIDTHS[width]
    pool = ((layers, pool_blocks, BLOCK_SIZE, kv_heads * head_dim), jnp.bfloat16)

    def decode(q, k, v, tables, pos, layer):
        return paged_flash_decode(q, k, v, tables, pos, layer=layer, interpret=False)

    text = compile_for_chip(
        decode, ((slots, 1, heads, head_dim), jnp.bfloat16), pool, pool,
        ((slots, blocks_per_row), jnp.int32), ((slots,), jnp.int32), ((), jnp.int32),
    )
    # the kernel reads the pool where it lies: the chip keeps this shape
    # row-major, so flattening it is a bitcast and nothing of its size is made
    flat = f"bf16[{layers * pool_blocks},{BLOCK_SIZE},{kv_heads * head_dim}]"
    made = [
        line for line in text.splitlines()
        if f" = {flat}" in line and " bitcast(" not in line and " parameter(" not in line
    ]
    assert not made, made[:2]
    # one kernel a layer, under the name the benchmark's readers look for, and
    # everything of size inside it (PR 31): the walk's two chunk buffers, the
    # accumulator and the query rows fit the 16 MB a kernel gets unasked
    calls = re.findall(r"^\s*%paged_decode[.\d]* = .*$", text, flags=re.M)
    assert len(calls) == 1, calls
    assert "vmem_limit_bytes" not in calls[0]
    scoped = re.search(r'"used_scoped_memory_configs":\[([^\]]*)\]', calls[0])
    sizes = [int(n) for n in re.findall(r'"size":"(\d+)"', scoped.group(1))]
    assert sizes and sum(sizes) < 16 << 20, sizes


def test_paged_flash_decode_compiles_on_a_latent_pool(compile_for_chip):
    """The A.X-K1 cell's call (PR 35): 64 slots of 4,096 positions under 64 query
    heads, one row of 640 a position (576 and zeros: whole tiles of 128 lanes)
    whose first 512 columns are its value, seven layers' rows in one pool of 2.3 GB
    that nothing may copy, and no pool of values at all. At 576 the chip lays the
    rows out 640 wide all the same and the kernel's block copy is refused for a
    slice that is not whole tiles: the row is stored at the width it occupies."""
    slots, heads, row, value, layers, blocks = 64, 64, 640, 512, 7, 16385

    def decode(q, rows, tables, pos, layer):
        return paged_flash_decode(q, rows, None, tables, pos, layer=layer, value_dim=value,
                                  scale=0.13, interpret=False)

    operands = (((slots, 1, heads, row), jnp.bfloat16),
                ((layers, blocks, BLOCK_SIZE, row), jnp.bfloat16),
                ((slots, 256), jnp.int32), ((slots,), jnp.int32), ((), jnp.int32))
    text = compile_for_chip(decode, *operands)
    flat = f"bf16[{layers * blocks},{BLOCK_SIZE},{row}]"
    made = [line for line in text.splitlines()
            if f" = {flat}" in line and " bitcast(" not in line and " parameter(" not in line]
    assert not made, made[:2]
    calls = re.findall(r"^\s*%paged_decode[.\d]* = .*$", text, flags=re.M)
    assert len(calls) == 1 and f"bf16[{slots},{heads},{value}]" in calls[0], calls
    with pytest.raises(Exception, match="aligned to tiling"):
        narrow = list(operands)
        narrow[0], narrow[1] = ((slots, 1, heads, 576), jnp.bfloat16), ((layers, blocks, BLOCK_SIZE, 576), jnp.bfloat16)
        compile_for_chip(decode, *narrow)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16_pool", "int8_pool"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_paged_flash_verify_compiles(compile_for_chip, width, quantized):
    heads, kv_heads, head_dim, pool, tables, pos, scales = _pool_operands(width, quantized)

    def verify(q, k, v, win_k, win_v, tables, pos, *scales):
        kwargs = dict(zip(("k_scale", "v_scale"), scales))
        return paged_flash_verify(
            q, k, v, win_k, win_v, tables, pos, interpret=False, **kwargs
        )

    window = ((SLOTS, WINDOW, kv_heads, head_dim), jnp.bfloat16)
    compile_for_chip(
        verify, ((SLOTS, WINDOW, heads, head_dim), jnp.bfloat16), pool, pool,
        window, window, tables, pos, *scales,
    )


# five rows: padded up to one eight-row tile. Qwen2's 152k vocabulary: more
# fast memory than the 16 MB a kernel gets unasked (and 15 s of compiling)
@pytest.mark.parametrize(
    "width,rows",
    [("gpt2_large", 8), ("gpt2_large", 5), ("mistral_7b", 8), ("qwen2_7b", 8),
     ("lfm2_8b_a1b", 32)],
)
def test_fused_sample_compiles(compile_for_chip, width, rows):
    vocab = WIDTHS[width][3]
    logits = ((rows, vocab), jnp.float32)
    knob = lambda dtype: ((rows,), dtype)  # noqa: E731
    compile_for_chip(
        functools.partial(fused_sample, interpret=False),
        logits, logits, knob(jnp.float32), knob(jnp.int32), knob(jnp.float32),
    )


# LFM2's expert layer at its published widths: 12 expert layers of 32 experts,
# 8.5 GB that nothing may copy. 128 rows (32 slots x 4 experts) is the decode
# step's shape, 2048 (a bucket of 512) the prefill's.
@pytest.mark.parametrize("tokens", [32, 512], ids=["decode_32_slots", "prefill_512"])
def test_dropless_moe_compiles_on_the_stacked_experts(compile_for_chip, monkeypatch, tokens):
    from accelerate_tpu.ops import grouped_matmul
    from accelerate_tpu.ops.moe import dropless_moe

    # the layer resolves `interpret` by the platform, which is the CPU here
    monkeypatch.setattr(grouped_matmul, "_resolve_interpret", lambda interpret: False)
    layers, experts, hidden, width = 12, 32, 2048, 1792

    def layer(x, router, bias, w1, w3, w2, index):
        return dropless_moe(x, router, bias, w1, w3, w2, layer=index, num_selected=4)

    up = ((layers, experts, hidden, width), jnp.bfloat16)
    text = compile_for_chip(
        layer, ((tokens, hidden), jnp.bfloat16), ((hidden, experts), jnp.bfloat16),
        ((experts,), jnp.bfloat16), up, up, ((layers, experts, width, hidden), jnp.bfloat16),
        ((), jnp.int32),
    )
    # the three grouped matmuls are the repo's own kernel, under the name that
    # chipbench/metrics/moe_gmm_roofline.serve.py looks for; none is left to the
    # compiler's ragged-dot
    assert len(re.findall(r"%moe_gmm[.\d]* = ", text)) == 3
    assert "ragged-dot" not in text
    # and is handed the whole stack as layers x experts groups: a layer's slice
    # of it as a kernel's operand would be a copy of 235 MB
    stack = rf"= bf16\[({layers},{experts}|{layers * experts}|{experts}),({hidden},{width}|{width},{hidden})\]"
    made = [
        line.strip()[:160] for line in text.splitlines()
        if re.search(stack, line) and " bitcast(" not in line and " parameter(" not in line
    ]
    assert not made, made[:2]
