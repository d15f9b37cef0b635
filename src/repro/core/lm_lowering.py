"""One chip's step of an LM-family `ArchConfig`, lowered to an op graph.

    graph = lower_step(cfg, StepPlan(decodes=(40_000, 9_000), chunk=4096,
                                     prefix=16_384), share)

A step processes the rows of its running decodes (one token each, over
its cache) and at most one prefill chunk (``chunk`` tokens of a prompt
whose first ``prefix`` tokens are already in the cache).  The graph
holds the embedding, then per layer (DeepSeek-V2, arXiv 2405.04434
§2.1-2.2):

1. the input norm;
2. ``q_proj``, ``kv_a_proj_with_mqa`` (to the latent and the rope key),
   the latent norm, rope on the rope dims;
3. for the chunk's rows, the prefill form: ``kv_b_proj`` decompresses
   the latent cache of prefix + chunk, then ``mla_prefill``;
4. for the decodes' rows, the absorbed form: ``W_UK`` absorbed into the
   queries, ``mla_decode`` over the latent cache, then ``W_UV``;
5. ``o_proj`` and the residual; the post-attention norm;
6. a dense SwiGLU in the leading dense layers; elsewhere ``moe_router``,
   the dispatch all-to-all, ``moe_routed`` for the experts held here at
   the step's routing load, the combine all-to-all and the shared
   experts' SwiGLU;
7. the residual.

Then the final norm and the head over the rows that emit a token.
Expert parallelism is the chip's share of a stated deployment: every
chip of the expert-parallel group runs a step of the same shape (data-
parallel attention), routes over all experts, and computes the experts
it holds for the tokens that all chips route to them.

Every op carries a ``layer`` param (-1 outside the layers).  Activations
are bfloat16.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import List, Sequence, Tuple

from repro.configs.base import ArchConfig
from repro.core.ir import OpGraph

ACT_DTYPE = "bfloat16"
ELEM_BYTES = 2


@dataclass(frozen=True)
class StepPlan:
    """What one step computes: the cache length of each running decode
    (before this step's token) and at most one prefill chunk."""

    decodes: Tuple[int, ...] = ()
    chunk: int = 0            # prefill tokens in this step
    prefix: int = 0           # the chunked prompt's tokens already cached
    emits: bool = False       # the chunk ends its prompt: its last row
                              # yields a token

    @property
    def tokens(self) -> int:
        return len(self.decodes) + self.chunk

    @property
    def emitting(self) -> int:
        return len(self.decodes) + int(self.chunk > 0 and self.emits)


@dataclass(frozen=True)
class ExpertShare:
    """The routed experts one chip holds, and how popular each is.

    ``popularity[l][e]`` is the chance that a token of MoE layer ``l``
    is routed to held expert ``e`` (at most 1; over all experts of the
    model they sum to ``top_k``).  Every chip of the ``group`` runs a
    step of the same shape, so held expert ``e`` receives
    ``round(group * tokens * popularity[l][e])`` tokens.
    """

    held: int
    group: int
    popularity: Tuple[Tuple[float, ...], ...]

    def loads(self, tokens: int) -> Tuple[Tuple[int, ...], ...]:
        """Tokens routed to each held expert, per MoE layer."""
        n = self.group * tokens
        return tuple(tuple(int(n * p + 0.5) for p in layer)
                     for layer in self.popularity)


def step_name(cfg: ArchConfig, plan: StepPlan, share: ExpertShare) -> str:
    """A name that determines the graph: the config, plan and share."""
    blob = repr((cfg, plan, share)).encode()
    return f"{cfg.name}.step.{hashlib.sha256(blob).hexdigest()[:20]}"


class _Builder:
    """Appends ops to ``g``, each stamped with the current layer."""

    def __init__(self, g: OpGraph):
        self.g = g
        self.layer = -1

    def op(self, op_type: str, inputs: Sequence[int],
           out_shapes: Sequence[Sequence[int]], **params) -> List[int]:
        params["layer"] = self.layer
        return self.g.add_op(op_type, inputs, out_shapes, params,
                             out_dtype=ACT_DTYPE)

    def matmul(self, x: Sequence[int], m: int, n: int, k: int, name: str,
               batch: int = 1, outs: Sequence[int] = ()) -> List[int]:
        shapes = [(m, w) for w in outs] or [(m, batch * n)]
        return self.op("matmul", x, shapes, m=m, n=n, k=k, batch=batch,
                       name=name)

    def swiglu(self, x: int, tokens: int, d: int, width: int,
               name: str) -> int:
        (gu,) = self.matmul([x], tokens, 2 * width, d, f"{name}.gate_up")
        (h,) = self.op("elementwise_lm", [gu], [(tokens, width)],
                       ew_kind="swiglu")
        (y,) = self.matmul([h], tokens, d, width, f"{name}.down")
        return y


def _prefill_attention(b: _Builder, cfg: ArchConfig, plan: StepPlan,
                       q: int, q_pe: int, latent: int, k_pe: int) -> int:
    """``kv_b_proj`` over the prompt's latent cache after this chunk,
    then the chunk's queries over the decompressed K/V."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kv_len = plan.prefix + plan.chunk
    ins = [latent]
    if plan.prefix:
        ins.append(b.g.add_input((plan.prefix, r + rope), ACT_DTYPE))
    (kv,) = b.matmul(ins, kv_len, H * (nope + v), r, "kv_b_proj")
    (o,) = b.op("mla_prefill", [q, q_pe, kv, k_pe],
                [(plan.chunk, H * v)], chunk=plan.chunk, prefix=plan.prefix,
                heads=H, qk_head_dim=nope + rope, rope_dim=rope,
                v_head_dim=v, elem_bytes=ELEM_BYTES)
    return o


def _decode_attention(b: _Builder, cfg: ArchConfig, plan: StepPlan,
                      q: int, q_pe: int, latent: int, k_pe: int) -> int:
    """The absorbed form: ``W_UK`` into the queries, attention over each
    sequence's latent cache, ``W_UV`` out of the latent space."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    n = len(plan.decodes)
    kv = [c + 1 for c in plan.decodes]          # the cache and this token
    (qa,) = b.matmul([q], n, r, nope, "w_uk_absorb", batch=H)
    cache = b.g.add_input((sum(plan.decodes), r + rope), ACT_DTYPE)
    (ol,) = b.op("mla_decode", [qa, q_pe, latent, k_pe, cache],
                 [(n, H * r)], seqs=n, kv_total=sum(kv), kv_max=max(kv),
                 heads=H, latent_dim=r, rope_dim=rope, elem_bytes=ELEM_BYTES)
    (o,) = b.matmul([ol], n, v, r, "w_uv_absorb", batch=H)
    return o


def _routed_experts(b: _Builder, cfg: ArchConfig, share: ExpertShare,
                    loads: Sequence[int], h: int, tokens: int) -> int:
    d, k = cfg.d_model, cfg.top_k
    (gate, idx) = b.op("moe_router", [h], [(tokens, k), (tokens, k)],
                       tokens=tokens, d_model=d, experts=cfg.num_experts,
                       top_k=k, elem_bytes=ELEM_BYTES)
    routed = sum(loads)
    x = h
    if share.group > 1:
        (x,) = b.op("collective", [h, gate, idx], [(routed, d)],
                    kind="all_to_all", participants=share.group,
                    bytes=tokens * k * d * ELEM_BYTES, name="dispatch")
    (y,) = b.op("moe_routed", [x], [(routed, d)],
                experts_held=share.held, active=sum(1 for n in loads if n),
                routed=routed, max_load=max(loads, default=0), d_model=d,
                d_ff=cfg.expert_d_ff, elem_bytes=ELEM_BYTES)
    if share.group > 1:
        (y,) = b.op("collective", [y], [(tokens, d)], kind="all_to_all",
                    participants=share.group,
                    bytes=tokens * k * d * ELEM_BYTES, name="combine")
    return y


def lower_step(cfg: ArchConfig, plan: StepPlan,
               share: ExpertShare) -> OpGraph:
    """The op graph of one chip's step (module docstring)."""
    if not cfg.kv_lora_rank:
        raise ValueError(f"{cfg.name}: lower_step lowers latent attention "
                         f"(kv_lora_rank > 0)")
    if cfg.q_lora_rank:
        raise ValueError(f"{cfg.name}: a compressed query (q_lora_rank) "
                         f"is not lowered")
    if plan.tokens == 0:
        raise ValueError("a step with no rows")
    n_moe = cfg.num_layers - cfg.first_k_dense
    if cfg.num_experts and len(share.popularity) != n_moe:
        raise ValueError(f"share has {len(share.popularity)} MoE layers, "
                         f"{cfg.name} {n_moe}")
    T, d, H = plan.tokens, cfg.d_model, cfg.num_heads
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    qk = cfg.qk_nope_head_dim + rope
    loads = share.loads(T)
    g = OpGraph(step_name(cfg, plan, share))
    b = _Builder(g)
    ids = g.add_input((T,), "int32")
    (x,) = b.op("embedding", [ids], [(T, d)], vocab=cfg.vocab_size, width=d,
                tokens=T)
    for layer in range(cfg.num_layers):
        b.layer = layer
        (h,) = b.op("norm", [x], [(T, d)])
        q, q_pe = b.matmul([h], T, H * qk, d, "q_proj",
                           outs=(H * cfg.qk_nope_head_dim, H * rope))
        lat, k_pe = b.matmul([h], T, r + rope, d, "kv_a_proj_with_mqa",
                             outs=(r, rope))
        (lat,) = b.op("norm", [lat], [(T, r)])
        q_pe, k_pe = b.op("rope", [q_pe, k_pe], [(T, H * rope), (T, rope)])
        attn = []
        if plan.chunk:
            attn.append(_prefill_attention(b, cfg, plan, q, q_pe, lat, k_pe))
        if plan.decodes:
            attn.append(_decode_attention(b, cfg, plan, q, q_pe, lat, k_pe))
        (o,) = b.matmul(attn, T, d, H * cfg.v_head_dim, "o_proj")
        (x,) = b.op("elementwise_lm", [x, o], [(T, d)], ew_kind="add")
        (h,) = b.op("norm", [x], [(T, d)])
        if layer < cfg.first_k_dense or not cfg.num_experts:
            ffn = [b.swiglu(h, T, d, cfg.d_ff, "mlp")]
        else:
            ffn = [_routed_experts(b, cfg, share,
                                   loads[layer - cfg.first_k_dense], h, T)]
            if cfg.n_shared_experts:
                ffn.append(b.swiglu(h, T, d, cfg.n_shared_experts
                                    * cfg.expert_d_ff, "shared_experts"))
        (x,) = b.op("elementwise_lm", [x, *ffn], [(T, d)], ew_kind="add")
    b.layer = -1
    (x,) = b.op("norm", [x], [(T, d)])
    if plan.emitting:
        (x,) = b.matmul([x], plan.emitting, cfg.vocab_size, d, "lm_head")
    g.mark_output(x)
    return g
