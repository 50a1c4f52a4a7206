"""Fig. 3: convergence of Algorithm 4 to the cap-out frequencies pi = N_c/N,
plus the shared-vs-independent coupling ablation (EXPERIMENTS.md
§Paper-validation)."""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit, time_call
from repro.core import estimate_pi, sequential_replay
from repro.data import make_synthetic_env


def main(n_events: int = 65_536, n_campaigns: int = 64) -> None:
    env = make_synthetic_env(jax.random.PRNGKey(0), n_events=n_events,
                             n_campaigns=n_campaigns, emb_dim=10)
    ref = sequential_replay(env.values, env.budgets, env.rule)
    frac = np.minimum(np.asarray(ref.cap_times) / n_events, 1.0)
    for coupling in ("shared", "independent"):
        for iters in (10, 40, 160):
            est, us = time_call(
                lambda: estimate_pi(
                    env.values, env.budgets, env.rule, jax.random.PRNGKey(7),
                    sample_size=int(n_events * 0.03), num_iters=iters,
                    eta=0.8, eta_decay=0.03, batch_size=64,
                    coupling=coupling),
                repeats=1)
            mae = float(np.abs(np.asarray(est.pi) - frac).mean())
            emit(f"fig3_vi_{coupling}_T{iters}", us, f"pi_mae={mae:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
