"""Training launcher.

Local (CPU/debug)::

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --smoke \
        --steps 100 --ckpt-dir /tmp/ckpt

Cluster posture: on real fleets this same entrypoint runs under
``jax.distributed.initialize()`` (one process per host), the mesh comes
from ``make_production_mesh()``, and the XLA flags below enable async
collectives so the latency-hiding scheduler overlaps the gradient
reduce-scatter with backward compute:

    LIBTPU_INIT_ARGS="--xla_enable_async_all_gather=true \
        --xla_tpu_enable_async_collective_fusion=true \
        --xla_tpu_overlap_compute_collective_tc=true"

Fault tolerance: checkpoints are atomic; on restart the trainer resumes
from the manifest (params, optimizer, data cursor).  Elastic rescale:
restore places leaves onto whatever mesh is live.
"""

from __future__ import annotations

import argparse
import logging

import jax
import numpy as np

from repro.configs import get_config
from repro.distributed.sharding import ShardingRules
from repro.launch.mesh import make_host_mesh
from repro.models.transformer import Model
from repro.training.data import SyntheticLM
from repro.training.optimizer import AdamWConfig
from repro.training.trainer import TrainConfig, Trainer
from repro.utils import place_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default=None,
                    help="cosine|wsd|constant (minicpm defaults to wsd)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", default=None, choices=[None, "bf16", "int8"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 (data x model)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    place_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    # the WSD schedule is minicpm's training preset (its paper contribution)
    schedule = args.schedule or ("wsd" if args.arch.startswith("minicpm") else "cosine")

    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.arch_id} params={n_params:,}")

    data = SyntheticLM(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.batch, seed=0,
        with_features=(
            (cfg.frontend.n_positions or None, cfg.frontend.feature_dim)
            if cfg.frontend else None),
        labels=cfg.frontend is not None or cfg.encoder_only,
    )
    tcfg = TrainConfig(
        opt=AdamWConfig(lr=args.lr, schedule=schedule, warmup_steps=20,
                        total_steps=args.steps),
        microbatches=args.microbatches,
        compression=args.compression,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=50,
    )
    rules = None
    if args.mesh:
        d, m = map(int, args.mesh.split("x"))
        rules = ShardingRules(make_host_mesh(d, m))
    trainer = Trainer(cfg, tcfg, params, data, rules=rules)
    if args.resume and args.ckpt_dir:
        step = trainer.restore()
        print(f"resumed from step {step}")

    trainer.run(
        args.steps,
        on_metrics=lambda s, m: print(
            f"step {s}: loss={m['loss']:.4f} lr={m['lr']:.2e} "
            f"gnorm={m['grad_norm']:.2f} dt={m['step_time_s']*1e3:.0f}ms"
        ),
    )
    if args.ckpt_dir:
        trainer.save(force=True)
        print(f"final checkpoint at step {trainer.step}")


if __name__ == "__main__":
    main()
