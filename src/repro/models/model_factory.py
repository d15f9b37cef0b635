"""Uniform model API over every architecture family.

`build_model(cfg)` returns a `Model` with:
  * init(key) → params                        (pure — eval_shape-able)
  * loss(params, batch) → (scalar, metrics)   (train step body)
  * forward(params, batch) → logits           (prefill)
  * init_cache(batch, max_len) → cache
  * decode_step(params, batch, cache) → (logits, cache)   (serve step body)
  * input_specs(shape) → batch of ShapeDtypeStructs       (dry-run stand-ins)

`input_specs` is where modality frontends are stubbed: VLM configs get
precomputed patch embeddings, whisper gets frame embeddings (assignment
directive).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, InputShape
from repro.models import encdec, hybrid, ssm, transformer
from repro.models.layers import dtype_of

Array = Any
Params = Dict[str, Any]


def cross_entropy(logits: Array, labels: Array) -> Array:
    """Mean token NLL. logits: (..., vocab) f32; labels: (...) int32.

    The gold logit is extracted with a one-hot reduction rather than
    take_along_axis: a per-token gather over a vocab-SHARDED logits
    tensor makes GSPMD replicate the logits, while the one-hot multiply
    + sum partitions cleanly (elementwise + reduce over the sharded
    vocab dim).
    """
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    return jnp.mean(logz - gold)


@dataclass
class Model:
    cfg: ArchConfig
    init: Callable[[Any], Params]
    loss: Callable[[Params, Dict[str, Array]], Tuple[Array, Dict[str, Array]]]
    forward: Callable[[Params, Dict[str, Array]], Array]
    init_cache: Callable[[int, int], Params]
    decode_step: Callable[[Params, Dict[str, Array], Params], Tuple[Array, Params]]
    input_specs: Callable[[InputShape], Dict[str, Any]]


def _token_specs(shape: InputShape, cfg: ArchConfig,
                 per_host: Optional[int] = None) -> Dict[str, Any]:
    b = shape.global_batch
    if shape.is_decode:
        return {"token": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    return {
        "tokens": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
    }


def build_model(cfg: ArchConfig) -> Model:
    if cfg.kv_lora_rank:
        raise NotImplementedError(
            f"{cfg.name}: latent attention has no trainable layer here; it "
            f"is lowered to op graphs for latency prediction "
            f"(repro.core.lm_lowering) against its plain reference "
            f"(repro.models.deepseek_v2_ref)")
    if cfg.family in ("dense", "moe", "vlm"):
        return _build_decoder(cfg)
    if cfg.family == "ssm":
        return _build_ssm(cfg)
    if cfg.family == "hybrid":
        return _build_hybrid(cfg)
    if cfg.family == "encdec":
        return _build_encdec(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")


# ---------------------------------------------------------------------------
# dense / moe / vlm — decoder-only transformer
# ---------------------------------------------------------------------------

def _build_decoder(cfg: ArchConfig) -> Model:
    is_vlm = cfg.family == "vlm"

    def init(key):
        return transformer.init_decoder(key, cfg)

    def forward(params, batch):
        logits, _ = transformer.decoder_forward(
            params, batch["tokens"], cfg,
            vision_embeds=batch.get("vision_embeds"))
        return logits

    def loss(params, batch):
        logits, aux = transformer.decoder_forward(
            params, batch["tokens"], cfg,
            vision_embeds=batch.get("vision_embeds"))
        nll = cross_entropy(logits, batch["labels"])
        total = nll + 0.01 * aux
        return total, {"nll": nll, "aux": aux}

    def init_cache(batch, max_len):
        return transformer.init_cache(cfg, batch, max_len)

    def decode_step(params, batch, cache):
        return transformer.decode_step(
            params, batch["token"], cache, cfg,
            vision_embeds=batch.get("vision_embeds"))

    def input_specs(shape: InputShape):
        specs = _token_specs(shape, cfg)
        if is_vlm:
            vs = cfg.vision_seq or 1024
            specs["vision_embeds"] = jax.ShapeDtypeStruct(
                (shape.global_batch, vs, cfg.d_model), jnp.dtype(cfg.compute_dtype))
        return specs

    return Model(cfg, init, loss, forward, init_cache, decode_step, input_specs)


# ---------------------------------------------------------------------------
# ssm — Mamba2
# ---------------------------------------------------------------------------

def _build_ssm(cfg: ArchConfig) -> Model:
    from repro.models.layers import embed, embed_init, norm_init, rms_norm, unembed

    def init(key):
        ke, kl, kh = jax.random.split(key, 3)
        p = {
            "embed": embed_init(ke, cfg.vocab_size, cfg.d_model, cfg.param_dtype),
            "layers": transformer._stack_layers(kl, cfg, cfg.num_layers, ssm.mamba_init),
            "final_norm": norm_init(cfg.d_model, cfg.param_dtype),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = embed_init(kh, cfg.vocab_size, cfg.d_model, cfg.param_dtype)
        return p

    def forward(params, batch):
        from repro.distributed.activations import constrain_logits, constrain_seq
        from repro.distributed.fsdp import gather_layer, pin_layer_stack
        dt = dtype_of(cfg)
        x = embed(params["embed"], batch["tokens"], dt)

        def body(x, lp):
            x = constrain_seq(x, cfg)
            return ssm.mamba_forward(gather_layer(lp, cfg), x, cfg), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x,
                            pin_layer_stack(params["layers"], cfg))
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        return constrain_logits(unembed(head, x)).astype(jnp.float32)

    def loss(params, batch):
        logits = forward(params, batch)
        nll = cross_entropy(logits, batch["labels"])
        return nll, {"nll": nll}

    def init_cache(batch, max_len):
        return ssm.init_mamba_cache(cfg, batch, cfg.num_layers)

    def decode_step(params, batch, cache):
        dt = dtype_of(cfg)
        x = embed(params["embed"], batch["token"], dt)

        def body(x, inp):
            lp, c = inp
            return ssm.mamba_decode(lp, x, cfg, c)

        x, ncache = jax.lax.scan(body, x, (params["layers"], cache))
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        return unembed(head, x[:, 0]).astype(jnp.float32), ncache

    return Model(cfg, init, loss, forward, init_cache, decode_step,
                 lambda shape: _token_specs(shape, cfg))


# ---------------------------------------------------------------------------
# hybrid — zamba2
# ---------------------------------------------------------------------------

def _build_hybrid(cfg: ArchConfig) -> Model:
    def init(key):
        return hybrid.init_hybrid(key, cfg)

    def forward(params, batch):
        return hybrid.hybrid_forward(params, batch["tokens"], cfg)

    def loss(params, batch):
        logits = forward(params, batch)
        nll = cross_entropy(logits, batch["labels"])
        return nll, {"nll": nll}

    def init_cache(batch, max_len):
        return hybrid.init_hybrid_cache(cfg, batch, max_len)

    def decode_step(params, batch, cache):
        return hybrid.hybrid_decode_step(params, batch["token"], cache, cfg)

    return Model(cfg, init, loss, forward, init_cache, decode_step,
                 lambda shape: _token_specs(shape, cfg))


# ---------------------------------------------------------------------------
# encdec — whisper
# ---------------------------------------------------------------------------

def _build_encdec(cfg: ArchConfig) -> Model:
    enc_seq = cfg.encoder_seq or 1500

    def init(key):
        return encdec.init_encdec(key, cfg)

    def forward(params, batch):
        memory = encdec.encode(params, batch["frames"], cfg)
        return encdec.decode_train(params, batch["tokens"], memory, cfg)

    def loss(params, batch):
        logits = forward(params, batch)
        nll = cross_entropy(logits, batch["labels"])
        return nll, {"nll": nll}

    def init_cache(batch, max_len):
        return encdec.init_encdec_cache(cfg, batch, max_len)

    def decode_step(params, batch, cache):
        return encdec.decode_step(params, batch["token"], cache,
                                  batch["memory"], cfg)

    def input_specs(shape: InputShape):
        b = shape.global_batch
        cdt = jnp.dtype(cfg.compute_dtype)
        if shape.is_decode:
            return {
                "token": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                "memory": jax.ShapeDtypeStruct((b, enc_seq, cfg.d_model), cdt),
            }
        # Teacher-forced train/prefill: decoder length is the shape's seq
        # (whisper's real decoder caps at 448; the assignment's shapes
        # exercise the backbone at the given lengths).
        return {
            "frames": jax.ShapeDtypeStruct((b, enc_seq, cfg.d_model), cdt),
            "tokens": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
        }

    return Model(cfg, init, loss, forward, init_cache, decode_step, input_specs)
