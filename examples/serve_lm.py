"""Serving example: continuous batching over a small model.

Submits a wave of requests with mixed prompt lengths, runs the engine,
prints per-request tokens + throughput.

  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.models import build_model
from repro.serving import ServeEngine
from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    cfg = get_arch("starcoder2-15b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, batch_slots=4, max_len=160)

    rng = np.random.default_rng(0)
    for i in range(10):
        plen = int(rng.integers(4, 24))
        engine.submit(rng.integers(0, cfg.vocab_size, plen), max_new_tokens=12)

    t0 = time.time()
    done = engine.run()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done)
    print(f"completed {len(done)} requests / {toks} tokens "
          f"in {dt:.1f}s ({toks/dt:.1f} tok/s)")
    for r in done[:4]:
        print(f"  req {r.uid}: prompt[{len(r.prompt)}] → {r.generated}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
