"""End-to-end training driver (deliverable b): train a ~100M-param dense
LM for a few hundred steps on CPU with the full production substrate —
resumable data pipeline, AdamW + cosine schedule, atomic checkpoints,
straggler watchdog. Interrupt it and re-run: it resumes from the last
checkpoint with an identical loss trajectory.

    PYTHONPATH=src python examples/train_lm.py [steps]
"""

import dataclasses
import sys
from pathlib import Path

from repro.configs import ARCHS
from repro.runtime import Trainer, TrainerConfig


def main():
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 200

    # ~100M-param member of the minicpm (llama-like) family
    cfg = dataclasses.replace(
        ARCHS["minicpm-2b"],
        name="minicpm-100m",
        n_layers=8,
        d_model=512,
        n_heads=8,
        n_kv_heads=8,
        head_dim=64,
        d_ff=1536,
        vocab=8192,
        dtype="float32",
    )
    n_params = cfg.n_params
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M")

    tcfg = TrainerConfig(seq_len=128, batch=8, lr=3e-4, warmup=20,
                         total_steps=steps, checkpoint_every=50)
    trainer = Trainer(cfg, tcfg, Path("results/ckpt_train_lm"))
    if trainer.start_step:
        print(f"resumed from checkpoint at step {trainer.start_step}")
    metrics = trainer.run()
    for m in metrics[:: max(len(metrics) // 10, 1)]:
        print(f"step {m['step']:4d} loss {m['loss']:.4f} "
              f"gnorm {m['gnorm']:.2f} {m['dt']*1e3:.0f}ms")
    print(f"final loss {metrics[-1]['loss']:.4f} "
          f"(start {metrics[0]['loss']:.4f}); "
          f"stragglers observed: {trainer.straggler_steps}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
