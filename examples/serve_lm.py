"""Budget-capped batched serving: requests as burnout variables.

Each request carries a token budget and exits irreversibly (budget/EOS) —
the serving analogue of campaign cap-out. The scheduler runs the
SORT2AGGREGATE playbook: estimate exit steps (uncertainty-relaxed,
shared-uniform coupling), sort them, pick K static compaction points, and
serve each fixed-shape segment with one compiled program.

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro.configs import reduced_config
from repro.models import build_model
from repro.serve.engine import (ServeEngine, estimate_exit_steps,
                                plan_compactions, wasted_slot_steps)


def main():
    t0 = time.time()
    cfg = reduced_config("stablelm-1.6b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_len=96)

    rng = np.random.default_rng(0)
    n_req = 16
    budgets = rng.integers(8, 64, size=n_req)

    print("== plan (sort -> refine -> aggregate for serving) ==")
    exits = estimate_exit_steps(budgets, eos_survival=0.97)
    plan = plan_compactions(exits, max_segments=4,
                            total_steps=int(budgets.max()))
    naive = plan_compactions(exits, max_segments=1,
                             total_steps=int(budgets.max()))
    # evaluate against 'true' exits (here: the budgets — greedy LM on random
    # init rarely emits the reserved EOS)
    w_plan = wasted_slot_steps(plan, budgets.astype(np.float64))
    w_naive = wasted_slot_steps(naive, budgets.astype(np.float64))
    print(f"   compaction points: {plan.compaction_points}")
    print(f"   wasted slot-steps: static={w_naive}  planned={w_plan} "
          f"({100 * (1 - w_plan / max(w_naive, 1)):.0f}% saved)")

    print("== serve the first segment (fixed shape) ==")
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                          (n_req, 8), 0, cfg.vocab_size)}
    steps = plan.segments[0][1] - plan.segments[0][0]
    toks = eng.generate(batch, num_steps=min(steps, 24))
    print(f"   generated {toks.shape} tokens in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
