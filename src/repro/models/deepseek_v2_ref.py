"""Plain reference of one DeepSeek-V2 decoder layer (arXiv 2405.04434,
§2.1-2.2), in float32 `jax.numpy` under
``jax.default_matmul_precision("highest")``: no kernels, cache manager
or batching.  It is what `repro.core.lm_lowering` is checked against.

* Latent attention (MLA) in both published forms: `mla_naive`
  decompresses K/V from the latent (``kv_b_proj``) and attends with
  qk dim nope + rope; `mla_absorbed` folds ``W_UK`` into the queries,
  attends over the latent cache itself (``kv_lora_rank + rope`` wide)
  and applies ``W_UV`` after.  The latent cache of a sequence is the
  pair (normed latent, roped key) of each of its tokens.
* The router (softmax scoring, greedy top-k), the routed experts a chip
  holds (a range of expert ids), the shared experts and the dense FFN.

Departures from the published model, none of which changes a shape or
a cost: rope without YaRN's frequency scaling and mscale, in the
rotate-half form (the published code permutes the rope dims into that
form first); the softmax scale ``1/sqrt(qk)`` without YaRN's mscale;
no capacity limit on the experts; the top-k weights not renormalized
(``norm_topk_prob`` false) and ``routed_scaling_factor`` 1, as
published for V2-Lite.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig

Params = Dict[str, Any]
# (expert id, token rows routed to it, their gate weights)
Assignment = List[Tuple[int, Any, Any]]


def init_layer(key: Any, cfg: ArchConfig, *, dense: bool) -> Params:
    """Seeded random weights of one layer, each scaled by 1/sqrt(fan-in)."""
    d, H = cfg.d_model, cfg.num_heads
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r, E, f = cfg.kv_lora_rank, cfg.num_experts, cfg.expert_d_ff
    shapes = {
        "attn_norm": (d,), "q_proj": (d, H * (nope + rope)),
        "kv_a": (d, r + rope), "kv_norm": (r,),
        "kv_b": (r, H * (nope + v)), "o_proj": (H * v, d), "ffn_norm": (d,),
    }
    if dense:
        shapes.update(gate=(d, cfg.d_ff), up=(d, cfg.d_ff),
                      down=(cfg.d_ff, d))
    else:
        s = cfg.n_shared_experts * f
        shapes.update(router=(d, E), e_gate=(E, d, f), e_up=(E, d, f),
                      e_down=(E, f, d), s_gate=(d, s), s_up=(d, s),
                      s_down=(s, d))
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("norm"):
            out[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         / np.sqrt(shape[-2]))
    return out


def rms_norm(x: Any, w: Any, eps: float) -> Any:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x: Any, pos: Any, theta: float) -> Any:
    """Rotate-half rope on the last dim; ``pos`` indexes x's first dim."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                      / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def project(p: Params, cfg: ArchConfig, h: Any, pos: Any
            ) -> Tuple[Any, Any, Any, Any]:
    """Queries (nope part, roped part) and the latent cache entries
    (normed latent, roped key) of the normed rows ``h``."""
    H, nope = cfg.num_heads, cfg.qk_nope_head_dim
    r = cfg.kv_lora_rank
    q = (h @ p["q_proj"]).reshape(h.shape[0], H, -1)
    q_pe = rope(q[..., nope:], pos, cfg.rope_theta)
    kv = h @ p["kv_a"]
    c = rms_norm(kv[:, :r], p["kv_norm"], cfg.norm_eps)
    k_pe = rope(kv[:, r:], pos, cfg.rope_theta)
    return q[..., :nope], q_pe, c, k_pe


def _softmax_attend(scores: Any, scale: float, mask: Optional[Any]) -> Any:
    s = scores * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    return jax.nn.softmax(s, axis=-1)


def mla_naive(p: Params, cfg: ArchConfig, q_nope: Any, q_pe: Any,
              c: Any, k_pe: Any, first: int,
              q_block: Optional[int] = None) -> Any:
    """Queries at positions ``first ..`` over the latent cache ``c``,
    ``k_pe`` (every earlier position and their own), decompressed by
    ``kv_b_proj``; returns (rows, heads * v).  ``q_block`` attends in
    blocks of that many queries, which bounds the score matrix's size
    and changes neither the values nor the operations."""
    H, nope, v = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    kv = (c @ p["kv_b"]).reshape(c.shape[0], H, nope + v)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe[:, None, :], (c.shape[0], H, k_pe.shape[-1]))],
        -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    n, K = q.shape[0], c.shape[0]
    step = q_block or n
    outs = []
    for s in range(0, n, step):
        qb = q[s:s + step]
        mask = (jnp.arange(K)[None, :]
                <= first + s + jnp.arange(qb.shape[0])[:, None])[None]
        w = _softmax_attend(jnp.einsum("qhd,khd->hqk", qb, k),
                            1.0 / np.sqrt(q.shape[-1]), mask)
        outs.append(jnp.einsum("hqk,khd->qhd", w, kv[..., nope:]))
    return jnp.concatenate(outs).reshape(n, H * v)


def _absorb(p: Params, cfg: ArchConfig) -> Tuple[Any, Any]:
    H, nope, v = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    wb = p["kv_b"].reshape(cfg.kv_lora_rank, H, nope + v)
    return wb[..., :nope], wb[..., nope:]


def mla_absorbed(p: Params, cfg: ArchConfig, q_nope: Any, q_pe: Any,
                 caches: Sequence[Tuple[Any, Any]]) -> Any:
    """One query per sequence (row i of ``q_nope``/``q_pe``) over that
    sequence's whole latent cache ``caches[i]`` (its own entry last):
    ``W_UK`` absorbed into the query, ``W_UV`` applied to the latent
    output; returns (rows, heads * v)."""
    w_uk, w_uv = _absorb(p, cfg)
    qa = jnp.einsum("nhd,rhd->nhr", q_nope, w_uk)
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    outs = []
    for i, (c, k_pe) in enumerate(caches):
        qc = jnp.concatenate([qa[i], q_pe[i]], -1)           # (H, r + rope)
        kc = jnp.concatenate([c, k_pe], -1)                  # (K, r + rope)
        w = _softmax_attend(qc @ kc.T, scale, None)
        outs.append(w @ c)                                   # (H, r)
    o = jnp.einsum("nhr,rhv->nhv", jnp.stack(outs), w_uv)
    return o.reshape(len(caches), -1)


def swiglu(h: Any, gate: Any, up: Any, down: Any) -> Any:
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def router_probs(p: Params, h: Any) -> Any:
    return jax.nn.softmax(h @ p["router"], axis=-1)


def route(probs: Any, top_k: int, held: range) -> Assignment:
    """Greedy top-k of the scores, as each held expert's rows and gates
    (computed eagerly: the rows' counts depend on the values)."""
    pr = np.asarray(probs)
    top = np.argsort(-pr, axis=1, kind="stable")[:, :top_k]
    out = []
    for e in held:
        rows = np.nonzero((top == e).any(axis=1))[0]
        out.append((e, jnp.asarray(rows), jnp.asarray(pr[rows, e])))
    return out


def routed_experts(p: Params, h: Any, assignment: Assignment) -> Any:
    """Sum over the assigned experts of gate * SwiGLU_e(rows)."""
    y = jnp.zeros_like(h)
    for e, rows, gate in assignment:
        xe = h[rows]
        ye = swiglu(xe, p["e_gate"][e], p["e_up"][e], p["e_down"][e])
        y = y.at[rows].add(gate[:, None] * ye)
    return y


def shared_experts(p: Params, h: Any) -> Any:
    return swiglu(h, p["s_gate"], p["s_up"], p["s_down"])


def decoder_layer(p: Params, cfg: ArchConfig, x: Any, *, chunk: int = 0,
                  prefix: Optional[Tuple[Any, Any]] = None,
                  decode_pos: Sequence[int] = (),
                  decode_caches: Sequence[Tuple[Any, Any]] = (),
                  assignment: Optional[Assignment] = None,
                  held: Optional[range] = None,
                  q_block: Optional[int] = None) -> Any:
    """One layer over a step's rows: the first ``chunk`` rows are a
    prefill chunk after the latent cache ``prefix`` (naive form), the
    rest decodes at positions ``decode_pos`` over ``decode_caches``
    (absorbed form).  MoE layers route over every expert and compute
    the ``held`` ones (default all); ``assignment`` gives the routing
    instead (its gates are read from this layer's router).  ``q_block``
    is `mla_naive`'s."""
    P = 0 if prefix is None else prefix[0].shape[0]
    pos = jnp.concatenate([P + jnp.arange(chunk),
                           jnp.asarray(decode_pos, dtype=jnp.int32)])
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q_nope, q_pe, c, k_pe = project(p, cfg, h, pos)
    outs = []
    if chunk:
        cc, kc = c[:chunk], k_pe[:chunk]
        if prefix is not None:
            cc = jnp.concatenate([prefix[0], cc])
            kc = jnp.concatenate([prefix[1], kc])
        outs.append(mla_naive(p, cfg, q_nope[:chunk], q_pe[:chunk], cc, kc,
                              P, q_block))
    if len(decode_pos):
        caches = [(jnp.concatenate([cc, c[chunk + i:chunk + i + 1]]),
                   jnp.concatenate([kc, k_pe[chunk + i:chunk + i + 1]]))
                  for i, (cc, kc) in enumerate(decode_caches)]
        outs.append(mla_absorbed(p, cfg, q_nope[chunk:], q_pe[chunk:],
                                 caches))
    x = x + jnp.concatenate(outs) @ p["o_proj"]
    h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    if "router" not in p:
        return x + swiglu(h, p["gate"], p["up"], p["down"])
    probs = router_probs(p, h)
    if assignment is None:
        assignment = route(probs, cfg.top_k,
                           held if held is not None else range(cfg.num_experts))
    else:
        assignment = [(e, rows, probs[rows, e]) for e, rows, _ in assignment]
    return x + routed_experts(p, h, assignment) + shared_experts(p, h)


def latent_cache(p: Params, cfg: ArchConfig, x: Any) -> Tuple[Any, Any]:
    """The latent cache entries of the layer inputs ``x`` at positions
    0 .. len(x) - 1."""
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    _, _, c, k_pe = project(p, cfg, h, jnp.arange(x.shape[0]))
    return c, k_pe


def layer_flops(cfg: ArchConfig, *, dense: bool, chunk: int, prefix: int,
                decodes: Sequence[int], loads: Sequence[int] = (),
                q_block: Optional[int] = None,
                sharding: Optional[Any] = None) -> float:
    """XLA's count of the FLOPs of `decoder_layer` for one step, from
    abstract shapes (nothing runs): a chunk of ``chunk`` rows after
    ``prefix`` cached tokens, decodes over caches of ``decodes`` tokens,
    and ``loads[e]`` rows routed to expert e.  The count is the lowered
    module's where the backend gives one (the CPU), else the compiled
    program's (a TPU, given as the shapes' ``sharding``, which need not
    be attached); ``q_block`` keeps the prefill form's scores small
    enough to compile for one chip."""
    f32 = jnp.float32
    T = chunk + len(decodes)
    r_k = (cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    params = jax.eval_shape(lambda k: init_layer(k, cfg, dense=dense),
                            jax.random.PRNGKey(0))
    specs = {
        "p": params,
        "x": jax.ShapeDtypeStruct((T, cfg.d_model), f32),
        "prefix": (tuple(jax.ShapeDtypeStruct((prefix, w), f32) for w in r_k)
                   if prefix else None),
        "caches": [tuple(jax.ShapeDtypeStruct((n, w), f32) for w in r_k)
                   for n in decodes],
        "rows": [jax.ShapeDtypeStruct((n,), jnp.int32) for n in loads],
    }
    if sharding is not None:
        specs = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), specs)

    def step(p, x, prefix, caches, rows):
        assignment = None if dense else [(e, r, None)
                                         for e, r in enumerate(rows)]
        return decoder_layer(p, cfg, x, chunk=chunk, prefix=prefix,
                             decode_pos=list(decodes), decode_caches=caches,
                             assignment=assignment, q_block=q_block)

    with jax.default_matmul_precision("highest"):
        lowered = jax.jit(step).lower(**specs)
        cost = lowered.cost_analysis() or lowered.compile().cost_analysis()
    return float(cost["flops"])
