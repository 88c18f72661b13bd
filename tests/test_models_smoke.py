"""Per-architecture smoke tests: REDUCED configs of the same family.

For each of the 10 assigned architectures: instantiate the reduced
variant (<=2 blocks / <=512 d_model / <=4 experts), run one forward and
one train step on CPU, assert output shapes and absence of NaNs; for
decoders additionally check prefill+decode agreement with the full
forward pass (cache correctness).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import Model
from repro.models.layers import embed
from repro.training import AdamWConfig, adamw_update, init_adamw

B, S = 2, 64


def _batch(cfg, rng):
    labels = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    if cfg.embedding_inputs:
        return {"embeds": jax.random.normal(rng, (B, S, cfg.d_model)) * 0.02,
                "labels": labels}
    return {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
            "labels": labels}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    spec = {
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }[arch]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.d_ff, cfg.vocab_size) == spec
    assert cfg.source, "config must cite its source"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_reduced_limits(arch):
    cfg = get_smoke_config(arch)
    assert cfg.num_layers <= 2 * len(cfg.layer_pattern)
    assert cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.num_experts <= 4


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init_params(rng, jnp.float32)
    batch = _batch(cfg, rng)

    logits, _ = jax.jit(lambda p, b: model.forward(
        p, b.get("tokens"), b.get("embeds")))(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits)))

    # one optimizer step
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    state = init_adamw(params)

    @jax.jit
    def step(p, s, b):
        (loss, _), g = jax.value_and_grad(model.loss, has_aux=True)(p, b)
        p, s, m = adamw_update(opt, p, g, s)
        return p, s, loss

    params2, _, loss = step(params, state, batch)
    assert np.isfinite(float(loss))
    changed = sum(
        int(not np.array_equal(np.asarray(a), np.asarray(b)))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert changed > 0


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if get_smoke_config(a).is_decoder])
def test_smoke_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    if cfg.moe is not None:  # avoid capacity-drop mismatches in the check
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe,
            capacity_factor=float(cfg.moe.num_experts)
            / cfg.moe.num_experts_per_tok))
    model = Model(cfg)
    rng = jax.random.PRNGKey(1)
    params = model.init_params(rng, jnp.float32)
    toks = jax.random.randint(rng, (B, S + 1), 0, cfg.vocab_size)

    if cfg.embedding_inputs:
        full_logits, _ = model.forward(
            params, embeds=embed(params["embed"], toks))
        pre = dict(embeds=embed(params["embed"], toks[:, :S]))
    else:
        full_logits, _ = model.forward(params, tokens=toks)
        pre = dict(tokens=toks[:, :S])

    cache = model.init_cache(B, S + 8, jnp.float32)
    lp, cache = model.prefill(params, cache=cache, **pre)
    np.testing.assert_allclose(np.asarray(lp[:, 0]),
                               np.asarray(full_logits[:, S - 1]),
                               atol=2e-3, rtol=1e-3)
    lg, _ = model.decode_step(params, toks[:, S:S + 1], cache,
                              jnp.array(S, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg[:, 0]),
                               np.asarray(full_logits[:, S]),
                               atol=2e-3, rtol=1e-3)


def test_encoder_has_no_decode():
    cfg = get_smoke_config("hubert-xlarge")
    assert not cfg.is_decoder
    assert not cfg.causal


def test_moe_dropless_at_decode():
    """Decode groups have one token: routing never drops (serving fidelity)."""
    from repro.models.moe import capacity_per_group
    cfg = get_smoke_config("deepseek-moe-16b")
    assert capacity_per_group(1, cfg.moe) >= cfg.moe.num_experts_per_tok
