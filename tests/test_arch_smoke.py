"""Per-architecture smoke tests: REDUCED config of the same family,
one forward/train step + one decode step on CPU; shapes + no NaNs.
(The FULL configs are exercised only via the dry-run.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, INPUT_SHAPES, all_cells, get_arch, shape_applicable
from repro.models import build_model

# The configs that train: `shape_applicable` turns the others away
# (latent attention is lowered for latency prediction only).
ALL_ARCHS = sorted(a for a in ARCHS
                   if shape_applicable(ARCHS[a], INPUT_SHAPES["train_4k"])[0])


def _batch_for(cfg, b=2, s=32):
    batch = {"tokens": jnp.ones((b, s), jnp.int32),
             "labels": jnp.ones((b, s), jnp.int32)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.zeros((b, cfg.vision_seq, cfg.d_model),
                                           jnp.bfloat16)
    if cfg.family == "encdec":
        batch["frames"] = jnp.zeros((b, cfg.encoder_seq, cfg.d_model),
                                    jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_train_step(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    assert float(loss) > 0
    logits = model.forward(params, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_decode_step(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = 2
    cache = model.init_cache(b, 64)
    batch = {"token": jnp.ones((b, 1), jnp.int32)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.zeros((b, cfg.vision_seq, cfg.d_model),
                                           jnp.bfloat16)
    if cfg.family == "encdec":
        batch["memory"] = jnp.zeros((b, cfg.encoder_seq, cfg.d_model),
                                    jnp.bfloat16)
    step = jax.jit(model.decode_step)
    logits, cache = step(params, batch, cache)
    # feed a DIFFERENT token: with identical tokens V is constant so the
    # attention output is v for any weights and logits repeat exactly.
    batch2 = dict(batch, token=jnp.full((b, 1), 7, jnp.int32))
    logits2, cache = step(params, batch2, cache)
    assert logits.shape == (b, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert not np.allclose(np.asarray(logits), np.asarray(logits2))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_gradients_flow(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, b=1, s=16)
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    norms = [float(jnp.linalg.norm(g.astype(jnp.float32)))
             for g in jax.tree_util.tree_leaves(grads)]
    assert all(np.isfinite(n) for n in norms), f"{arch}: non-finite grads"
    assert sum(norms) > 0, f"{arch}: zero gradients"


def test_latent_attention_configs_are_not_built():
    with pytest.raises(NotImplementedError, match="latent attention"):
        build_model(get_arch("deepseek-v2-lite").reduced())
    cells = [c for c in all_cells() if c[0] == "deepseek-v2-lite"]
    assert len(cells) == len(INPUT_SHAPES)
    assert all(not ok and why.startswith("latent attention") for _, _, ok, why in cells)
