"""Lowered FLOPs of DeepSeek-V2-Lite layers against XLA's count of the
plain reference layer, at the published widths.

For a decode plan and a 16k chunk over a 128k prefix, each line gives
the summed `flops` feature of one layer's lowered ops (the dense layer
0, and an MoE layer with 16 of 64 experts held) beside XLA's count of
`repro.models.deepseek_v2_ref.decoder_layer` for the same step.
Nothing runs: the reference is compiled from abstract shapes.

  PYTHONPATH=src python examples/mla_layer_flops.py              # this backend
  PYTHONPATH=src python examples/mla_layer_flops.py --described  # a v5e, described

On a TPU the count is the compiled program's; `--described` compiles
for a described v5e instead of an attached device, so the TPU
compiler's count can be read without the chip.  The prefill form
attends in blocks of 256 queries so that it compiles for one chip.
"""
import argparse
import json

import jax

from repro.configs import get_arch
from repro.core.features import featurize
from repro.core.lm_lowering import ExpertShare, StepPlan, lower_step
from repro.models.deepseek_v2_ref import layer_flops

PLANS = {"decode": StepPlan(decodes=(16_000, 40_000, 131_000)),
         "prefill_16k_over_128k": StepPlan(chunk=16_384, prefix=131_072)}


def described_v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def lowered_flops(graph, layer: int) -> float:
    total = 0.0
    for n in graph.nodes:
        if n.param("layer") == layer:
            keys, vals = featurize(graph, n)
            total += sum(v for k, v in zip(keys, vals) if k == "flops")
    return total


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--described", action="store_true",
                    help="compile for a described v5e, not this backend")
    args = ap.parse_args()
    sharding, where = None, jax.devices()[0].device_kind
    if args.described:
        jax.config.update("jax_enable_compilation_cache", False)
        sharding, where = described_v5e(), "described v5e"
    cfg = get_arch("deepseek-v2-lite")
    # A fixed skew of routed load over the 16 held experts, per MoE layer.
    pop = tuple(tuple(min(1.0, 6 / 64 * (0.4 + 0.3 * ((e + l) % 5)))
                      for e in range(16))
                for l in range(cfg.num_layers - cfg.first_k_dense))
    share = ExpertShare(held=16, group=4, popularity=pop)
    for name, plan in PLANS.items():
        g = lower_step(cfg, plan, share)
        for layer, dense in ((0, True), (1, False)):
            low = lowered_flops(g, layer)
            loads = () if dense else share.loads(plan.tokens)[0]
            xla = layer_flops(cfg, dense=dense, chunk=plan.chunk,
                              prefix=plan.prefix, decodes=plan.decodes,
                              loads=loads, q_block=256, sharding=sharding)
            print(json.dumps({
                "where": where, "plan": name,
                "layer": "dense" if dense else "moe_16_held",
                "lowered": low, "xla": xla, "gap": (low - xla) / xla}),
                flush=True)


if __name__ == "__main__":
    main()
