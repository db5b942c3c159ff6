"""Per-architecture smoke tests: reduced configs, one forward/train/decode
step on CPU, asserting output shapes and absence of NaNs (deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import lm

B, S = 2, 32


def make_batch(cfg, key):
    kt, ke, kp, kl = jax.random.split(key, 4)
    batch = {}
    if cfg.frontend == "audio_stub":
        batch["embeds"] = jax.random.normal(ke, (B, S, cfg.d_model),
                                            jnp.float32)
    else:
        batch["tokens"] = jax.random.randint(kt, (B, S), 0, cfg.vocab)
    if cfg.frontend == "vision_stub":
        batch["patch_embeds"] = jax.random.normal(kp, (B, 8, cfg.d_model),
                                                  jnp.float32)
    batch["labels"] = jax.random.randint(kl, (B, S), 0, cfg.vocab)
    return batch


def route(cfg):
    """The selection bias a biased router's forward pass is handed (an
    untrained model's: zeros)."""
    if cfg.moe is not None and cfg.moe.biased:
        return {"route_bias": lm.init_route_state(cfg)["bias"]}
    return {}


@pytest.fixture(scope="module")
def arch_state():
    cache = {}

    def get(arch_id):
        if arch_id not in cache:
            cfg = get_smoke_config(arch_id)
            params = lm.init_params(cfg, jax.random.PRNGKey(0))
            batch = make_batch(cfg, jax.random.PRNGKey(1))
            cache[arch_id] = (cfg, params, batch)
        return cache[arch_id]

    return get


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_forward_shapes_and_finite(arch_state, arch_id):
    cfg, params, batch = arch_state(arch_id)
    h, c = lm.forward(cfg, params, batch, **route(cfg))
    assert h.shape == (B, S, cfg.d_model)
    assert bool(jnp.isfinite(h).all()), f"{arch_id}: non-finite hidden"
    assert c is None


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_train_step_loss_and_grads_finite(arch_state, arch_id):
    cfg, params, batch = arch_state(arch_id)
    loss, grads = jax.value_and_grad(
        lambda p: lm.loss_fn(cfg, p, batch, **route(cfg)))(params)
    assert np.isfinite(float(loss)), f"{arch_id}: loss={loss}"
    # a plausible CE at init: ~log(vocab)
    assert 0.1 * np.log(cfg.vocab) < float(loss) < 3.0 * np.log(cfg.vocab)
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves), \
        f"{arch_id}: non-finite grads"
    assert any(float(jnp.abs(g).max()) > 0 for g in leaves), \
        f"{arch_id}: all-zero grads"


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_prefill_then_decode(arch_state, arch_id):
    cfg, params, batch = arch_state(arch_id)
    logits, cache = lm.prefill(cfg, params, {k: v for k, v in batch.items()
                                             if k != "labels"},
                               **route(cfg))
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    # one decode step writing at position S-1... use a fresh slot by
    # rebuilding a longer cache
    cache2 = lm.init_cache(cfg, B, S + 4)
    tok = jnp.zeros((B,), jnp.int32)
    embeds = (jnp.zeros((B, 1, cfg.d_model), jnp.float32)
              if cfg.frontend == "audio_stub" else None)
    logits2, cache2 = lm.decode_step(cfg, params, cache2, tok,
                                     jnp.int32(0), embeds=embeds,
                                     **route(cfg))
    assert logits2.shape == (B, cfg.vocab)
    assert bool(jnp.isfinite(logits2).all())
    # decode twice more to exercise cache advance
    logits3, cache2 = lm.decode_step(cfg, params, cache2, tok,
                                     jnp.int32(1), embeds=embeds,
                                     **route(cfg))
    assert bool(jnp.isfinite(logits3).all())


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_config_shapes_are_exact(arch_id):
    """The FULL configs match the assignment table (no allocation)."""
    cfg = get_config(arch_id)
    table = {
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 0, 49155),
        "moonshot-v1-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "gemma2-9b": (42, 3584, 16, 8, 14336, 256000),
        "qwen2-7b": (28, 3584, 28, 4, 18944, 152064),
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "mamba2-780m": (48, 1536, 0, 0, 0, 50280),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
    }
    L, d, h, kv, ff, v = table[arch_id]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_ff,
            cfg.vocab) == (L, d, h, kv, ff, v)
    # per-arch extras
    if arch_id == "granite-moe-3b-a800m":
        assert cfg.moe.n_experts == 40 and cfg.moe.top_k == 8
        assert cfg.moe.d_expert == 512
    if arch_id == "moonshot-v1-16b-a3b":
        assert cfg.moe.n_experts == 64 and cfg.moe.top_k == 6
        assert cfg.moe.d_expert == 1408 and cfg.moe.n_shared == 2
        assert cfg.moe.score == "sigmoid" and cfg.moe.routed_scale == 2.446
        assert cfg.first_dense == 1 and cfg.norm_eps == 1e-5
        assert cfg.rope_theta == 50000.0 and not cfg.tie_embeddings
        assert cfg.mla.q_lora is None and cfg.mla.kv_lora == 512
        assert (cfg.mla.qk_nope, cfg.mla.qk_rope, cfg.mla.v_dim) == \
            (128, 64, 128)
    if arch_id == "hymba-1.5b":
        assert cfg.mamba.d_state == 16 and cfg.mixer == "hybrid"
    if arch_id == "mamba2-780m":
        assert cfg.mamba.d_state == 128 and cfg.mixer == "mamba"
    if arch_id == "gemma2-9b":
        assert cfg.attn_softcap == 50.0 and cfg.final_softcap == 30.0
        assert cfg.window_pattern == "gemma_alt"
    if arch_id == "qwen2-vl-7b":
        assert cfg.mrope_sections == (16, 24, 24)
    if arch_id == "minicpm3-4b":
        assert cfg.mla is not None and cfg.mla.kv_lora == 256
    if arch_id == "qwen2-7b":
        assert cfg.qkv_bias


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_param_specs_cover_param_tree(arch_id):
    """Every parameter leaf has a PartitionSpec of matching rank."""
    cfg = get_config(arch_id).with_tp(16)
    shapes = lm.param_shapes(cfg)
    specs = lm.param_specs(cfg)
    flat_s, tdef_s = jax.tree.flatten(shapes)
    flat_p, tdef_p = jax.tree.flatten(specs, is_leaf=lambda x: x is None or
                                      hasattr(x, "_normalized_spec_for_aval"))
    assert tdef_s == jax.tree.structure(
        jax.tree.map(lambda s: 0, specs,
                     is_leaf=lambda x: hasattr(x, "index")))


def test_param_counts_plausible():
    """Logical parameter counts land near the published sizes."""
    expected = {
        "gemma2-9b": (8.5e9, 10.5e9),
        "qwen2-7b": (7.0e9, 8.0e9),
        "llama3.2-1b": (1.0e9, 1.6e9),
        "mamba2-780m": (0.6e9, 0.95e9),
        "minicpm3-4b": (3.5e9, 4.8e9),
        "musicgen-medium": (1.2e9, 2.2e9),
        "hymba-1.5b": (1.2e9, 2.1e9),
        "granite-moe-3b-a800m": (2.5e9, 3.9e9),
        "moonshot-v1-16b-a3b": (15e9, 17e9),
        "qwen2-vl-7b": (7.0e9, 8.0e9),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9},{hi/1e9}]"


def test_moe_active_params():
    cfg = get_config("granite-moe-3b-a800m")
    active = cfg.active_param_count()
    assert 0.55e9 < active < 1.1e9, active / 1e9  # "a800m"
    cfg2 = get_config("moonshot-v1-16b-a3b")
    active2 = cfg2.active_param_count()
    assert 2.2e9 < active2 < 4.5e9, active2 / 1e9  # "a3b"
