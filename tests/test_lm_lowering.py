"""The lowering of a DeepSeek-V2 step (`repro.core.lm_lowering`) against
its plain reference (`repro.models.deepseek_v2_ref`), on the CPU.

* The absorbed decode through a latent cache gives what the naive form
  gives at the same positions, and a mixed step's rows give what each
  sequence's naive forward gives.
* Four shares of the experts add up to the uncut layer, the shared
  experts counted once.
* Per layer, the lowered ops' FLOPs equal XLA's count for the reference
  layer, at a toy size and at the published widths (from abstract
  shapes: the layer is lowered by JAX, never compiled or run), for a
  decode plan, a prefill chunk over a long prefix and a mixed plan; a
  lowering that leaves out the prefix's decompression, or counts decode
  in the naive form, fails the same comparison.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import lm_lowering
from repro.core.features import featurize
from repro.core.lm_lowering import ExpertShare, StepPlan, lower_step
from repro.models import deepseek_v2_ref as ref

PUBLISHED = get_arch("deepseek-v2-lite")
TOY = PUBLISHED.reduced()

# float32 under "highest" precision: the absorbed and naive forms sum
# the same products in another order.
REL = 1e-5
# The lowering leaves a few element-wise ops without a FLOP count (the
# residual adds, the dense and shared SwiGLU's activation, rope's
# angles) and norms its own way (5 an element; XLA counts about 4): at
# most 0.21% of a toy layer and 0.015% of a published one were seen.
FLOPS_TOL = {"toy": 5e-3, "published": 1e-3}


def _close(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.fixture(scope="module")
def layers():
    with jax.default_matmul_precision("highest"):
        k0, k1 = jax.random.split(jax.random.PRNGKey(7))
        return {"dense": ref.init_layer(k0, TOY, dense=True),
                "moe": ref.init_layer(k1, TOY, dense=False)}


def _layer(p, x, *, chunk=0, prefix=None, decode_pos=(), decode_caches=(),
           held=None):
    """`decoder_layer`; jitted for a dense layer, whose shapes do not
    depend on the values (one compile instead of one an op)."""
    def f(p, x, prefix, decode_caches):
        return ref.decoder_layer(p, TOY, x, chunk=chunk, prefix=prefix,
                                 decode_pos=decode_pos,
                                 decode_caches=decode_caches, held=held)

    if "router" not in p:
        f = jax.jit(f)
    return f(p, x, prefix, decode_caches)


def _x(seed, n):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, TOY.d_model))


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_absorbed_decode_equals_the_naive_form(layers, kind):
    p = layers[kind]
    seqs = [_x(1, 13), _x(2, 21)]
    with jax.default_matmul_precision("highest"):
        naive = [_layer(p, s, chunk=len(s)) for s in seqs]
        caches = [ref.latent_cache(p, TOY, s[:-1]) for s in seqs]
        got = _layer(
            p, jnp.stack([s[-1] for s in seqs]),
            decode_pos=[len(s) - 1 for s in seqs], decode_caches=caches)
    assert _close(got, jnp.stack([n[-1] for n in naive])) <= REL


def test_a_mixed_step_equals_each_sequence_naive(layers):
    """A chunk after a cached prefix and two decodes in one step."""
    p = layers["dense"]
    prompt, a, b = _x(3, 40), _x(4, 9), _x(5, 30)
    with jax.default_matmul_precision("highest"):
        want = [_layer(p, s, chunk=len(s)) for s in (prompt, a, b)]
        step = jnp.concatenate([prompt[32:], a[-1:], b[-1:]])
        got = _layer(
            p, step, chunk=8, prefix=ref.latent_cache(p, TOY,
                                                           prompt[:32]),
            decode_pos=[8, 29], decode_caches=[
                ref.latent_cache(p, TOY, a[:-1]),
                ref.latent_cache(p, TOY, b[:-1])])
    assert _close(got, jnp.concatenate(
        [want[0][32:], want[1][-1:], want[2][-1:]])) <= REL


def test_query_blocks_change_neither_values_nor_flops(layers):
    """Blocking the prefill form's queries (so that the published
    widths compile for one chip) computes the same layer."""
    p, x = layers["dense"], _x(7, 24)
    with jax.default_matmul_precision("highest"):
        whole = _layer(p, x, chunk=24)
        blocked = jax.jit(lambda p, x: ref.decoder_layer(
            p, TOY, x, chunk=24, q_block=5))(p, x)
    assert _close(blocked, whole) <= REL
    kw = dict(dense=True, chunk=24, prefix=40, decodes=(9,))
    assert ref.layer_flops(TOY, q_block=5, **kw) == ref.layer_flops(TOY, **kw)


def test_expert_shares_add_up_to_the_uncut_layer(layers):
    """Each chip routes over every expert and computes its own; with
    the shared experts (computed on every chip alike) counted once, the
    four shares' routed parts add up to the whole layer."""
    p, x = layers["moe"], _x(6, 24)
    E = TOY.num_experts
    with jax.default_matmul_precision("highest"):
        whole = _layer(p, x, chunk=24)
        base = _layer(p, x, chunk=24, held=range(0))
        parts = [_layer(p, x, chunk=24,
                        held=range(i * E // 4, (i + 1) * E // 4)) - base
                 for i in range(4)]
    assert all(float(jnp.max(jnp.abs(q))) > 0 for q in parts)
    assert _close(base + sum(parts), whole) <= REL


# -- FLOPs against XLA's count of the reference ------------------------------

def _share(cfg, held, group=4):
    layers = cfg.num_layers - cfg.first_k_dense
    base = cfg.top_k / cfg.num_experts
    return ExpertShare(held=held, group=group, popularity=tuple(
        tuple(min(1.0, base * (0.4 + 0.3 * ((e + l) % 5))) for e in range(held))
        for l in range(layers)))


SIZES = {
    "toy": (TOY, _share(TOY, TOY.num_experts // 4), {
        "decode": StepPlan(decodes=(5, 17, 33)),
        "prefill": StepPlan(chunk=8, prefix=64),
        "mixed": StepPlan(decodes=(5, 17), chunk=8, prefix=40, emits=True)}),
    "published": (PUBLISHED, _share(PUBLISHED, 16), {
        "decode": StepPlan(decodes=(16_000, 40_000, 131_000)),
        "prefill": StepPlan(chunk=16_384, prefix=131_072),
        "mixed": StepPlan(decodes=(16_000, 40_000), chunk=2048,
                          prefix=30_000, emits=True)}),
}


def _lowered_flops(graph, layer):
    total = 0.0
    for node in graph.nodes:
        if node.param("layer") == layer:
            names, vals = featurize(graph, node)
            total += sum(v for k, v in zip(names, vals) if k == "flops")
    return total


def _gap(size, plan_name, kind):
    """Relative gap of layer 0 (dense) or 1 (MoE) of the lowered step
    from XLA's FLOPs of the reference layer for the same step."""
    cfg, share, plans = SIZES[size]
    plan = plans[plan_name]
    layer = 0 if kind == "dense" else 1
    loads = () if kind == "dense" else share.loads(plan.tokens)[0]
    want = ref.layer_flops(cfg, dense=kind == "dense", chunk=plan.chunk,
                           prefix=plan.prefix, decodes=plan.decodes,
                           loads=loads)
    got = _lowered_flops(lower_step(cfg, plan, share), layer)
    return (got - want) / want


@pytest.mark.parametrize("kind", ["dense", "moe"])
@pytest.mark.parametrize("plan", ["decode", "prefill", "mixed"])
@pytest.mark.parametrize("size", ["toy", "published"])
def test_lowered_flops_match_xla_on_the_reference(size, plan, kind):
    assert abs(_gap(size, plan, kind)) <= FLOPS_TOL[size]


def _prefill_without_prefix(b, cfg, plan, q, q_pe, latent, k_pe):
    """Fault: ``kv_b_proj`` decompresses the chunk only."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    (kv,) = b.matmul([latent], plan.chunk, H * (nope + v), r, "kv_b_proj")
    (o,) = b.op("mla_prefill", [q, q_pe, kv, k_pe], [(plan.chunk, H * v)],
                chunk=plan.chunk, prefix=plan.prefix, heads=H,
                qk_head_dim=nope + rope, rope_dim=rope, v_head_dim=v)
    return o


def _decode_naive(b, cfg, plan, q, q_pe, latent, k_pe):
    """Fault: decode decompresses every cache and attends with qk dim
    nope + rope, as the prefill form does."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    n = len(plan.decodes)
    (kv,) = b.matmul([latent], sum(plan.decodes) + n, H * (nope + v), r,
                     "kv_b_proj")
    outs = [b.op("mla_prefill", [q, q_pe, kv, k_pe], [(1, H * v)], chunk=1,
                 prefix=c, heads=H, qk_head_dim=nope + rope, rope_dim=rope,
                 v_head_dim=v)[0] for c in plan.decodes]
    (o,) = b.op("elementwise_lm", outs, [(n, H * v)], ew_kind="concat")
    return o


@pytest.mark.parametrize("fault,plan", [
    ("prefix_decompression_left_out", "prefill"),
    ("prefix_decompression_left_out", "mixed"),
    ("decode_in_naive_form", "decode"),
    ("decode_in_naive_form", "mixed")])
@pytest.mark.parametrize("size", ["toy", "published"])
def test_a_planted_lowering_fault_fails_the_flops_check(monkeypatch, size,
                                                        fault, plan):
    if fault == "prefix_decompression_left_out":
        monkeypatch.setattr(lm_lowering, "_prefill_attention",
                            _prefill_without_prefix)
    else:
        monkeypatch.setattr(lm_lowering, "_decode_attention", _decode_naive)
    assert abs(_gap(size, plan, "moe")) > FLOPS_TOL[size]


def test_step_graph_shape_at_the_published_widths():
    cfg, share, plans = SIZES["published"]
    g = lower_step(cfg, plans["mixed"], share)
    g.validate()
    counts = g.op_type_counts()
    L, moe = cfg.num_layers, cfg.num_layers - cfg.first_k_dense
    assert counts["mla_prefill"] == counts["mla_decode"] == L
    assert counts["moe_router"] == counts["moe_routed"] == moe
    assert counts["collective"] == 2 * moe
    head = g.nodes[-1]
    assert head.param("name") == "lm_head" and head.param("m") == 3
    assert head.param("n") == cfg.vocab_size
    routed = [n for n in g.nodes if n.op_type == "moe_routed"]
    assert routed[0].param("routed") == sum(share.loads(2050)[0])
    assert routed[0].param("max_load") == max(share.loads(2050)[0])
    assert lower_step(cfg, plans["mixed"], share).name == g.name
    assert lower_step(cfg, plans["decode"], share).name != g.name


def test_published_param_counts():
    """The catalog gives 15.7 B parameters.  The active count includes
    the embedding and the head (2.66 B); with the head alone 2.45 B,
    and 2.24 B without either."""
    cfg = PUBLISHED
    assert cfg.num_params() == pytest.approx(15.7e9, rel=0.01)
    emb = cfg.vocab_size * cfg.d_model
    assert cfg.active_params() == pytest.approx(2.66e9, rel=0.005)
    assert cfg.active_params() - emb == pytest.approx(2.45e9, rel=0.005)
    assert cfg.active_params() - 2 * emb == pytest.approx(2.24e9, rel=0.005)
