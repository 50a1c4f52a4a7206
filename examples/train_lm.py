"""End-to-end training driver example: a ~100M-class backbone (reduced
same-family config on CPU) trained for a few hundred steps with async
checkpointing, an injected worker failure, and checkpoint-resume — the
fault-tolerance loop the pod driver uses.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import tempfile
import time

import numpy as np

from repro.configs import reduced_config
from repro.fault import FailureInjector
from repro.launch.train import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()

    cfg = reduced_config(args.arch)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        print(f"== training reduced {args.arch} for {args.steps} steps "
              f"(failure injected at step {args.steps // 2}) ==")
        t0 = time.time()
        _, losses = train_loop(
            cfg, steps=args.steps, global_batch=8, seq_len=64,
            ckpt_dir=ckpt_dir, microbatches=2, lr=1e-3, ckpt_every=25,
            failure_injector=FailureInjector(
                schedule={args.steps // 2: 3}),
            log_every=25)
        first = np.mean(losses[:10])
        last = np.mean(losses[-10:])
        print(f"== done in {time.time() - t0:.1f}s: "
              f"loss {first:.3f} -> {last:.3f} "
              f"({len(losses)} effective steps incl. replayed) ==")
        assert last < first, "loss should decrease"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
