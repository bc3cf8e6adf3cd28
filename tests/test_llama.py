import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from accelerate_tpu import Accelerator
from accelerate_tpu.models.llama import (
    LlamaConfig,
    convert_hf_state_dict,
    create_llama,
    init_llama_params,
    llama_apply,
    llama_loss,
)
from accelerate_tpu.parallelism_config import ParallelismConfig


def test_forward_shapes():
    cfg = LlamaConfig.tiny()
    model = create_llama(cfg, seed=0)
    ids = jnp.zeros((2, 16), dtype=jnp.int32)
    logits = model(ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_scan_matches_unrolled():
    cfg_scan = LlamaConfig.tiny(scan_layers=True, compute_dtype=jnp.float32)
    cfg_loop = LlamaConfig.tiny(scan_layers=False, compute_dtype=jnp.float32)
    model = create_llama(cfg_scan, seed=1)
    ids = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % cfg_scan.vocab_size
    a = llama_apply(cfg_scan, model.params, ids)
    b = llama_apply(cfg_loop, model.params, ids)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2, rtol=2e-2)


def test_attention_impls_agree():
    cfg_block = LlamaConfig.tiny(
        attention_impl="blockwise", attention_kv_block=8, compute_dtype=jnp.float32
    )
    cfg_xla = LlamaConfig.tiny(attention_impl="xla", compute_dtype=jnp.float32)
    model = create_llama(cfg_block, seed=2)
    ids = (jnp.arange(64, dtype=jnp.int32).reshape(2, 32) * 7) % cfg_block.vocab_size
    a = llama_apply(cfg_block, model.params, ids)
    b = llama_apply(cfg_xla, model.params, ids)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2, rtol=2e-2)


def test_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    model = create_llama(cfg, seed=3)
    ids = jnp.ones((1, 16), dtype=jnp.int32)
    ids2 = ids.at[0, 10].set(5)
    a = llama_apply(cfg, model.params, ids)
    b = llama_apply(cfg, model.params, ids2)
    np.testing.assert_allclose(np.asarray(a[0, :10]), np.asarray(b[0, :10]), atol=1e-5)
    assert not np.allclose(np.asarray(a[0, 10:]), np.asarray(b[0, 10:]), atol=1e-5)


@pytest.mark.slow
def test_llama_trains_with_fsdp_and_tp():
    """2-way FSDP × 2-way TP × 2-way DP-replicate on the 8-device mesh."""
    pcfg = ParallelismConfig(dp_replicate_size=2, dp_shard_size=2, tp_size=2)
    accelerator = Accelerator(parallelism_config=pcfg)
    cfg = LlamaConfig.tiny()
    model = create_llama(cfg, seed=0)
    optimizer = optax.adamw(1e-3)
    model, optimizer = accelerator.prepare(model, optimizer)

    # FSDP+TP actually sharded something
    specs = [str(s.spec) for s in jax.tree_util.tree_leaves(model.shardings)]
    assert any("tp" in s for s in specs)
    assert any("dp_shard" in s for s in specs)

    rng = np.random.default_rng(0)
    data = {"input_ids": rng.integers(0, cfg.vocab_size, size=(16, 32)).astype(np.int32)}
    loader = accelerator.prepare_data_loader(data, batch_size=8, drop_last=True)
    losses = []
    for epoch in range(3):
        for batch in loader:
            with accelerator.accumulate(model):
                loss = accelerator.backward(llama_loss, batch)
                optimizer.step()
                optimizer.zero_grad()
                losses.append(float(loss))
    assert losses[-1] < losses[0]  # learning


@pytest.mark.slow
def test_fused_step_llama():
    pcfg = ParallelismConfig(dp_shard_size=8)
    accelerator = Accelerator(parallelism_config=pcfg)
    cfg = LlamaConfig.tiny()
    model = create_llama(cfg, seed=0)
    optimizer = optax.adamw(1e-3)
    model, optimizer = accelerator.prepare(model, optimizer)
    step = accelerator.train_step(llama_loss, max_grad_norm=1.0)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, size=(8, 32)).astype(np.int32)}
    loader = accelerator.prepare_data_loader(batch, batch_size=8, drop_last=True)
    first = last = None
    for _ in range(5):
        for b in loader:
            loss = float(step(b))
            first = first if first is not None else loss
            last = loss
    assert last < first


def test_sliding_window_receptive_field():
    """With a 1-layer model and window W, logits at position t must be
    independent of tokens more than W back (the Mistral guarantee the
    attention masks implement)."""
    cfg = LlamaConfig.tiny(num_hidden_layers=1, sliding_window=8,
                           compute_dtype=jnp.float32)
    params = init_llama_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    a = rng.integers(4, cfg.vocab_size, size=(1, 32)).astype(np.int32)
    b = a.copy()
    b[0, 0] = (b[0, 0] + 1) % cfg.vocab_size  # perturb position 0
    la = np.asarray(llama_apply(cfg, params, a))
    lb = np.asarray(llama_apply(cfg, params, b))
    # positions >= 8 can no longer see position 0
    np.testing.assert_allclose(la[0, 8:], lb[0, 8:], atol=1e-5)
    assert np.abs(la[0, :8] - lb[0, :8]).max() > 1e-4


def test_sliding_window_decode_matches_full_forward():
    """KV-cache decode applies the same window mask as the full forward."""
    from accelerate_tpu.models.llama import llama_decode_step

    cfg = LlamaConfig.tiny(sliding_window=6, compute_dtype=jnp.float32)
    params = init_llama_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(4, cfg.vocab_size, size=(2, 16)).astype(np.int32))
    full = np.asarray(llama_apply(cfg, params, ids))

    h, kvh, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    L = cfg.num_hidden_layers
    cache = {
        "k": jnp.zeros((L, 2, 16, kvh, hd), jnp.float32),
        "v": jnp.zeros((L, 2, 16, kvh, hd), jnp.float32),
    }
    for t in range(16):
        step_logits, cache = llama_decode_step(
            cfg, params, cache, ids[:, t : t + 1], jnp.int32(t)
        )
        np.testing.assert_allclose(
            np.asarray(step_logits), full[:, t], atol=1e-4, rtol=1e-4
        )


def test_hf_mistral_logits_parity():
    """Mistral-7B family: llama arch + GQA + sliding window. A random HF
    MistralForCausalLM converts via the SAME convert_hf_state_dict and
    logits match with the window active (seq > window)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=8,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = transformers.MistralForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(0).integers(0, 128, size=(2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=8,
        rms_norm_eps=hf_cfg.rms_norm_eps,  # MistralConfig defaults 1e-6
        compute_dtype=jnp.float32, attention_impl="xla",
    )
    flat = {k: v.numpy() for k, v in hf_model.state_dict().items()}
    params = convert_hf_state_dict(cfg, flat)
    ours = np.asarray(llama_apply(cfg, params, ids.astype(np.int32)))
    np.testing.assert_allclose(ours, hf_logits, atol=2e-4)


def test_hf_qwen2_logits_parity():
    """Qwen2 family: llama arch + GQA + q/k/v projection biases. A random
    HF Qwen2ForCausalLM converts through the shared converter (biases ride
    the same rotate-half unpermute as the kernels) and logits match."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    # random (nonzero) biases so the bias path is actually exercised
    with torch.no_grad():
        for layer in hf_model.model.layers:
            for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                         layer.self_attn.v_proj):
                proj.bias.normal_(0, 0.5)
    ids = np.random.default_rng(0).integers(0, 128, size=(2, 16))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, attention_bias=True,
        rms_norm_eps=hf_cfg.rms_norm_eps,
        compute_dtype=jnp.float32, attention_impl="xla",
    )
    flat = {k: v.numpy() for k, v in hf_model.state_dict().items()}
    params = convert_hf_state_dict(cfg, flat)
    ours = np.asarray(llama_apply(cfg, params, ids.astype(np.int32)))
    np.testing.assert_allclose(ours, hf_logits, atol=2e-4)

    # export round-trip: biases come back in HF layout
    from accelerate_tpu.models.llama import export_hf_state_dict

    back = export_hf_state_dict(cfg, params)
    for i in range(2):
        for name in ("q_proj", "k_proj", "v_proj"):
            key = f"model.layers.{i}.self_attn.{name}.bias"
            np.testing.assert_allclose(
                back[key], flat[key], atol=1e-6,
                err_msg=f"{key} did not round-trip",
            )


def test_attention_bias_training_and_decode():
    """attention_bias=True trains (grads flow into the biases) and the
    decode path applies the same biases (decode == full forward)."""
    from accelerate_tpu.models.llama import llama_decode_step

    cfg = LlamaConfig.tiny(attention_bias=True, compute_dtype=jnp.float32)
    params = init_llama_params(cfg, jax.random.key(0))
    assert "bias" in params["layers"]["attn"]["q_proj"]
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(4, cfg.vocab_size, size=(2, 8)).astype(np.int32))
    # make biases nonzero so the check is meaningful
    params["layers"]["attn"]["q_proj"]["bias"] = (
        0.3 * jax.random.normal(jax.random.key(1),
                                params["layers"]["attn"]["q_proj"]["bias"].shape)
    )
    full = np.asarray(llama_apply(cfg, params, ids))

    h, kvh, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    cache = {
        "k": jnp.zeros((cfg.num_hidden_layers, 2, 8, kvh, hd), jnp.float32),
        "v": jnp.zeros((cfg.num_hidden_layers, 2, 8, kvh, hd), jnp.float32),
    }
    for t in range(8):
        step_logits, cache = llama_decode_step(
            cfg, params, cache, ids[:, t : t + 1], jnp.int32(t)
        )
        np.testing.assert_allclose(np.asarray(step_logits), full[:, t],
                                   atol=1e-4, rtol=1e-4)

    def loss(p):
        out = llama_apply(cfg, p, ids)
        return jnp.mean(out.astype(jnp.float32) ** 2)

    g = jax.grad(loss)(params)
    gb = np.asarray(g["layers"]["attn"]["v_proj"]["bias"])
    assert np.abs(gb).max() > 0


def test_convert_rejects_dropped_biases():
    """A bias-bearing checkpoint with attention_bias=False must fail loudly,
    not silently produce diverging logits."""
    cfg_b = LlamaConfig.tiny(attention_bias=True)
    from accelerate_tpu.models.llama import export_hf_state_dict

    params = init_llama_params(cfg_b, jax.random.key(0))
    flat = export_hf_state_dict(cfg_b, params)
    cfg_nb = LlamaConfig.tiny(attention_bias=False)
    with pytest.raises(ValueError, match="attention_bias"):
        convert_hf_state_dict(cfg_nb, flat)


def test_rope_scaling_llama3_matches_hf():
    """llama3-type rope scaling (Llama-3.1): converted HF checkpoint with
    rope_scaling active must match logits at positions beyond the original
    context geometry's comfort zone."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    scaling = {
        "rope_type": "llama3", "factor": 4.0,
        "low_freq_factor": 1.0, "high_freq_factor": 4.0,
        "original_max_position_embeddings": 16,
    }
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_scaling=dict(scaling),
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = transformers.LlamaForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(0).integers(0, 128, size=(2, 48))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_scaling=dict(scaling),
        rms_norm_eps=hf_cfg.rms_norm_eps,
        compute_dtype=jnp.float32, attention_impl="xla",
    )
    flat = {k: v.numpy() for k, v in hf_model.state_dict().items()}
    params = convert_hf_state_dict(cfg, flat)
    ours = np.asarray(llama_apply(cfg, params, ids.astype(np.int32)))
    np.testing.assert_allclose(ours, hf_logits, atol=2e-4)


def test_rope_scaling_decode_matches_full():
    from accelerate_tpu.models.llama import llama_decode_step

    cfg = LlamaConfig.tiny(
        compute_dtype=jnp.float32,
        rope_scaling={"rope_type": "linear", "factor": 2.0},
    )
    params = init_llama_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(4, cfg.vocab_size, size=(2, 8)).astype(np.int32))
    full = np.asarray(llama_apply(cfg, params, ids))
    kvh, hd, L = cfg.num_key_value_heads, cfg.head_dim, cfg.num_hidden_layers
    cache = {
        "k": jnp.zeros((L, 2, 8, kvh, hd), jnp.float32),
        "v": jnp.zeros((L, 2, 8, kvh, hd), jnp.float32),
    }
    for t in range(8):
        step_logits, cache = llama_decode_step(
            cfg, params, cache, ids[:, t : t + 1], jnp.int32(t)
        )
        np.testing.assert_allclose(np.asarray(step_logits), full[:, t],
                                   atol=1e-4, rtol=1e-4)
    # scaling actually changes the geometry vs unscaled
    plain = np.asarray(llama_apply(LlamaConfig.tiny(compute_dtype=jnp.float32),
                                   params, ids))
    assert np.abs(plain - full).max() > 1e-3


def test_rope_scaling_requires_explicit_type():
    cfg = LlamaConfig.tiny(rope_scaling={"factor": 8.0})
    ids = np.zeros((1, 8), np.int32)
    params = init_llama_params(LlamaConfig.tiny(), jax.random.key(0))
    with pytest.raises(ValueError, match="rope_type"):
        llama_apply(cfg, params, ids)


def test_hf_gemma_logits_parity():
    """Gemma family: decoupled head_dim, GeGLU, zero-centered (1+w)
    RMSNorm, sqrt(d)-scaled embeddings, tied head — all through the shared
    converter, torch-verified."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.GemmaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16,  # decoupled: 4 x 16 = 64 != hidden 32
        max_position_embeddings=64, attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = transformers.GemmaForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(0).integers(0, 128, size=(2, 16))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()

    cfg = LlamaConfig.gemma_7b(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64,
        rms_norm_eps=hf_cfg.rms_norm_eps,
        compute_dtype=jnp.float32, attention_impl="xla",
    )
    flat = {k: v.numpy() for k, v in hf_model.state_dict().items()}
    params = convert_hf_state_dict(cfg, flat)
    ours = np.asarray(llama_apply(cfg, params, ids.astype(np.int32)))
    np.testing.assert_allclose(ours, hf_logits, atol=3e-4)


def test_gemma_config_trains_and_decodes():
    from accelerate_tpu.models.llama import llama_decode_step

    cfg = LlamaConfig.gemma_7b(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    assert cfg.head_dim == 32 and cfg.rms_norm_offset
    params = init_llama_params(cfg, jax.random.key(0))
    # offset norms initialize zero-centered
    assert float(jnp.abs(params["layers"]["input_norm"]["scale"]).max()) == 0.0
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(4, 256, size=(2, 8)).astype(np.int32))
    full = np.asarray(llama_apply(cfg, params, ids))
    assert np.isfinite(full).all()

    kvh, hd, L = cfg.num_key_value_heads, cfg.head_dim, cfg.num_hidden_layers
    cache = {"k": jnp.zeros((L, 2, 8, kvh, hd), jnp.float32),
             "v": jnp.zeros((L, 2, 8, kvh, hd), jnp.float32)}
    for t in range(8):
        step_logits, cache = llama_decode_step(
            cfg, params, cache, ids[:, t : t + 1], jnp.int32(t))
        np.testing.assert_allclose(np.asarray(step_logits), full[:, t],
                                   atol=1e-4, rtol=1e-4)

    def loss(p):
        return jnp.mean(llama_apply(cfg, p, ids).astype(jnp.float32) ** 2)

    g = jax.grad(loss)(params)
    assert np.isfinite(np.asarray(g["layers"]["mlp"]["gate_proj"]["kernel"])).all()


def test_preset_overrides_rederive_head_dim():
    """Resizing a preset through its factory must re-derive head_dim (a
    stale inherited value silently breaks q/k/v shapes)."""
    cfg = LlamaConfig.llama3_1_8b(hidden_size=64, num_attention_heads=4)
    assert cfg.head_dim == 16
    with pytest.raises(ValueError, match="silu-only"):
        LlamaConfig.tiny(num_experts=4, hidden_act="gelu_tanh")


def test_hf_gemma2_logits_parity():
    """Gemma-2 family: everything Gemma-1 has plus attention/final logit
    softcapping, sandwich (pre+post) block norms, alternating local/global
    attention, and the decoupled query_pre_attn_scalar attention scale —
    torch-verified against transformers Gemma2ForCausalLM."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.Gemma2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64,
        sliding_window=8,  # < seq so local/global layers really differ
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        query_pre_attn_scalar=24.0,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = transformers.Gemma2ForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(0).integers(0, 128, size=(2, 16))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()

    cfg = LlamaConfig.gemma2_9b(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64,
        sliding_window=8, query_pre_attn_scalar=24.0,
        rms_norm_eps=hf_cfg.rms_norm_eps,
        compute_dtype=jnp.float32, attention_impl="xla",
    )
    flat = {k: v.numpy() for k, v in hf_model.state_dict().items()}
    params = convert_hf_state_dict(cfg, flat)
    ours = np.asarray(llama_apply(cfg, params, ids.astype(np.int32)))
    np.testing.assert_allclose(ours, hf_logits, atol=3e-4)

    # round-trip export: re-import equals the import
    from accelerate_tpu.models.llama import export_hf_state_dict

    back = export_hf_state_dict(cfg, params)
    params2 = convert_hf_state_dict(cfg, {k: np.asarray(v) for k, v in back.items()})
    for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(params2)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_gemma2_trains_and_decodes():
    """Alternating windows + softcaps agree between the full forward (pairs
    scan) and the decode path (per-layer sliding flags), and training is
    finite; flash/blockwise/xla agree on the capped scores."""
    from accelerate_tpu.models.llama import llama_decode_step

    cfg = LlamaConfig.gemma2_9b(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, max_position_embeddings=64, sliding_window=8,
        query_pre_attn_scalar=16.0, compute_dtype=jnp.float32,
    )
    params = init_llama_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(4, 256, size=(2, 16)).astype(np.int32))
    full = np.asarray(llama_apply(cfg, params, ids))
    assert np.isfinite(full).all() and np.abs(full).max() <= 30.0 + 1e-3

    # all three attention impls agree under softcap + alternating windows
    for impl in ("blockwise", "flash"):
        cfg_i = dataclasses.replace(
            cfg, attention_impl=impl,
            attention_kv_block=16, attention_block_q=16,
        )
        got = np.asarray(llama_apply(cfg_i, params, ids))
        np.testing.assert_allclose(got, full, atol=2e-5)

    # decode parity with the full forward at every position
    kvh, hd, L = cfg.num_key_value_heads, cfg.head_dim, cfg.num_hidden_layers
    cache = {"k": jnp.zeros((L, 2, 16, kvh, hd), jnp.float32),
             "v": jnp.zeros((L, 2, 16, kvh, hd), jnp.float32)}
    for t in range(16):
        step_logits, cache = llama_decode_step(
            cfg, params, cache, ids[:, t : t + 1], jnp.int32(t))
        np.testing.assert_allclose(np.asarray(step_logits), full[:, t],
                                   atol=1e-4, rtol=1e-4)

    def loss(p):
        return jnp.mean(llama_apply(cfg, p, ids).astype(jnp.float32) ** 2)

    g = jax.grad(loss)(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


def test_gemma2_chunked_ce_matches_dense():
    """The fused chunked CE must train against the SAME softcapped logits
    the dense path and inference serve (the protocol dict carries the cap)."""
    from accelerate_tpu.models.llama import llama_loss

    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, sliding_window=8,
        query_pre_attn_scalar=16.0, compute_dtype=jnp.float32,
    )
    cfg_dense = LlamaConfig.gemma2_9b(**base)
    cfg_chunk = LlamaConfig.gemma2_9b(**base, use_chunked_ce=True, ce_chunk_size=64)
    params = init_llama_params(cfg_dense, jax.random.key(0))
    ids = jnp.asarray(
        np.random.default_rng(0).integers(4, 256, size=(2, 16)).astype(np.int32)
    )
    batch = {"input_ids": ids}
    dense = float(llama_loss(
        lambda i, **kw: llama_apply(cfg_dense, params, i, **kw), batch
    ))
    chunked = float(llama_loss(
        lambda i, **kw: llama_apply(cfg_chunk, params, i, **kw), batch,
        ce_chunk_size=64,
    ))
    np.testing.assert_allclose(chunked, dense, rtol=1e-5)


def test_gqa_grouped_attention_bit_parity_with_repeat_kv_cache():
    """PR 4 rewrote decode attention to broadcast over the GQA group dim
    instead of physically tiling KV n_rep x (repeat_kv_cache). The grouped
    einsum must reproduce the tiled reference bit-for-bit, including the
    head ordering (head j = group j//n_rep, repeat j%n_rep) and the per-row
    causal mask."""
    from jax import lax

    from accelerate_tpu.models.llama import repeat_kv_cache

    rng = np.random.default_rng(0)
    b, s, h, kvh, hd, kl = 2, 1, 8, 2, 16, 12
    n_rep = h // kvh
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(b, kl, kvh, hd)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(b, kl, kvh, hd)), jnp.float32)
    pos = jnp.asarray([5, 9], jnp.int32)

    # reference: the old path — materialize KV n_rep x, then plain MHA
    rk, rv = repeat_kv_cache(ck, n_rep), repeat_kv_cache(cv, n_rep)
    ref_scores = jnp.einsum("bqhd,bkhd->bhqk", q, rk).astype(jnp.float32)
    kp = lax.broadcasted_iota(jnp.int32, ref_scores.shape, 3)
    ref_scores = jnp.where(kp <= pos[:, None, None, None], ref_scores, -1e6)
    ref_probs = jax.nn.softmax(ref_scores, axis=-1)
    ref_out = jnp.einsum(
        "bhqk,bkhd->bqhd", ref_probs.astype(rv.dtype), rv
    ).reshape(b, s, h * hd)

    # grouped: the shipped path — no tiling, broadcast over the group dim
    qg = q.reshape(b, s, kvh, n_rep, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, ck).astype(jnp.float32)
    kp5 = lax.broadcasted_iota(jnp.int32, scores.shape, 4)
    scores = jnp.where(kp5 <= pos[:, None, None, None, None], scores, -1e6)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bgrqk,bkgd->bqgrd", probs.astype(cv.dtype), cv
    ).reshape(b, s, h * hd)

    # (b, g, r, q, k) with g, r adjacent flattens to the reference head
    # order. Guarded: scores (the part the GQA rewrite touches: head
    # mapping, mask, softmax input) are the same 16 products summed for the
    # same (query head, key) pairs. XLA's CPU backend adds them in another
    # order for the 5-d grouped einsum than for the 4-d tiled one, which
    # costs the last bits: equal to 4 float32 ulps of the largest score. A
    # wrong head or mask is off by whole units.
    ref_scores = np.asarray(ref_scores)
    np.testing.assert_allclose(
        np.asarray(scores.reshape(b, h, s, kl)), ref_scores, rtol=0,
        atol=4 * np.finfo(np.float32).eps * np.abs(ref_scores[ref_scores > -1e6]).max(),
    )
    # the value contraction accumulates over k in a different loop order
    # than the tiled reference, so only ULP-level drift is allowed there
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), rtol=1e-6, atol=1e-6
    )


def test_gqa_decode_step_matches_full_forward():
    """End-to-end check that the grouped-GQA decode path (vector positions,
    per-row KV writes) reproduces the full forward's logits on a GQA config
    with rows at DIFFERENT positions — the shape the continuous engine
    drives."""
    from accelerate_tpu.models.llama import llama_decode_step, llama_prefill_at

    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    assert cfg.num_attention_heads != cfg.num_key_value_heads  # really GQA
    params = init_llama_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(3)
    max_len = 24
    lens = np.array([5, 9])
    ids = np.zeros((2, 12), np.int32)
    for i, n in enumerate(lens):
        ids[i, :n] = rng.integers(1, cfg.vocab_size, size=n)

    logits, cache = llama_prefill_at(
        cfg, params, jnp.asarray(ids), max_len, jnp.asarray(lens - 1)
    )
    # feed each row's argmax back at its own position
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    step_logits, _ = llama_decode_step(
        cfg, params, cache, tok[:, None], jnp.asarray(lens, jnp.int32)
    )
    # reference: full forward over prompt + token, read the last position
    for i, n in enumerate(lens):
        row = np.concatenate([ids[i, :n], np.asarray(tok)[i : i + 1]])
        full = llama_apply(cfg, params, jnp.asarray(row[None]))
        np.testing.assert_allclose(
            np.asarray(step_logits)[i], np.asarray(full)[0, -1], rtol=2e-5, atol=2e-5
        )
