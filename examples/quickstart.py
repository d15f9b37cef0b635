"""Quickstart: the paper's pipeline end-to-end in ~2 minutes.

1. sample synthetic NAS architectures (paper §4.3.2),
2. profile them into a persistent ProfileStore (re-running this script
   is free: warm signatures are never re-measured),
3. train per-op-type predictors (paper §4.2) via LatencyService.build,
4. predict end-to-end latency of unseen architectures — the exact
   NAS-time use case — and report MAPE,
5. deduce GPU-delegate kernels (fusion + selection) for one arch.

  PYTHONPATH=src python examples/quickstart.py
"""
import os

from repro.core.dataset import synthetic_graphs
from repro.core.composition import mape
from repro.core.fusion import fuse_graph
from repro.core.profiler import DeviceSetting, ProfileSession
from repro.core.selection import apply_selection, get_device
from repro.pipeline import LatencyService
from repro.utils.compile_cache import enable_compile_cache

STORE = os.path.join(os.path.dirname(__file__), "..", "reports",
                     "quickstart_store.jsonl")


def main() -> None:
    print("== 1-3. profile 30 synthetic NAS archs into a store, train GBDT ==")
    graphs = synthetic_graphs(30, resolution=32)
    train, test = graphs[:24], graphs[24:]
    svc = LatencyService.build(
        graphs,
        DeviceSetting("cpu_f32", "float32", "op_by_op"),
        store=STORE,
        session=ProfileSession(repeats=2, inner=3),
        predictor="gbdt",
        overhead_model="affine",
        train_graphs=train,                    # hold out the last 6
    )
    print(f"store: {svc.store.stats()}  "
          f"(new measurements this run: {svc.session.measured_ops})")

    print("\n== 4. predict the 6 unseen archs in one batched query ==")
    reports = svc.predict_batch(test)
    y_true = [svc.store.get_arch(svc.default_setting, g.fingerprint()).e2e_s
              for g in test]
    y_pred = [r.e2e_s for r in reports]
    print(f"end-to-end latency MAPE on unseen archs: "
          f"{100 * mape(y_true, y_pred):.1f}%")
    for g, r, yt in zip(test, reports, y_true):
        print(f"  {g.name:24s} measured {1e3 * yt:6.2f} ms   "
              f"predicted {1e3 * r.e2e_s:6.2f} ms")
    again = svc.predict_e2e(test[0])
    print(f"repeat query served from cache: {again.from_cache} "
          f"({svc.cache_info()})")

    print("\n== 5. kernel deduction for arch #0 on a Mali-class GPU ==")
    g = graphs[0]
    groups, _ = fuse_graph(g)
    sel = apply_selection(g, get_device("mali_g76"))
    print(f"ops: {g.num_ops()}  → kernels after fusion: {len(groups)}")
    print(f"kernel mix after selection: {sel.op_type_counts()}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
