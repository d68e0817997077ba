"""Federated fine-tuning driver (the end-to-end trainer).

Runs heterogeneous-rank FedLoRA on the synthetic non-IID task with any of
the five aggregation methods, on the tiny CPU-scale ViT proxy (default) or
on a registered vision config at its published widths (``--arch
vit-base``, for a TPU).

  PYTHONPATH=src python -m repro.launch.train --method raflora --rounds 20
  PYTHONPATH=src python -m repro.launch.train --method flexlora --rounds 20 \
      --noniid dirichlet --alpha 0.1
  PYTHONPATH=src python -m repro.launch.train --arch vit-base \
      --backend kernel --rounds 3
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="registered vision config at published widths "
                         "(e.g. vit-base); default: the tiny proxy")
    ap.add_argument("--method", default="raflora",
                    choices=["fedavg", "hetlora", "flora", "flexlora",
                             "raflora"])
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--participation", type=float, default=0.25)
    ap.add_argument("--noniid", default="pathological",
                    choices=["iid", "dirichlet", "pathological"])
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--rank-levels", default="4,8,16,24,32")
    ap.add_argument("--backend", default="factored",
                    choices=["dense", "factored", "kernel"])
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.federation.experiment import build_experiment
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    levels = tuple(int(r) for r in args.rank_levels.split(","))
    exp = build_experiment(
        args.method, arch=args.arch,
        fl_overrides={"num_rounds": args.rounds, "num_clients": args.clients,
                      "participation": args.participation,
                      "partition": args.noniid,
                      "dirichlet_alpha": args.alpha, "seed": args.seed},
        lora_overrides={"rank_levels": levels,
                        "rank_probs": tuple([1 / len(levels)] * len(levels))},
        backend=args.backend)

    log = []
    t0 = time.time()
    for r in range(args.rounds):
        stats = exp.server.run_round()
        row = {"round": r, "loss": stats.mean_client_loss,
               "higher_rank_energy": float(
                   exp.server.energy.higher_rank_ratio[-1]),
               "lr": stats.lr, "wall_s": stats.wall_time_s}
        if (r + 1) % args.eval_every == 0 or r == args.rounds - 1:
            row["test_accuracy"] = exp.eval_accuracy()
        log.append(row)
        msg = (f"round {r:3d} loss={row['loss']:.4f} "
               f"1-rho={row['higher_rank_energy']:.3f}")
        if "test_accuracy" in row:
            msg += f" acc={row['test_accuracy']:.3f}"
        print(msg, flush=True)
    print(f"done in {time.time() - t0:.1f}s; "
          f"final acc={log[-1].get('test_accuracy'):.3f}")
    if args.checkpoint:
        exp.server.save(args.checkpoint)
        print(f"checkpoint -> {args.checkpoint}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(log, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
