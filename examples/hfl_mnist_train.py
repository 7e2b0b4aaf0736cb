"""The paper's experiment end-to-end: NOMA-HFL on MNIST-like data.

Trains the global classifier for ``--rounds`` global rounds under the fuzzy
client-edge association, PDD edge scheduling, and (optionally) a DDPG-trained
resource allocator; prints the per-round metrics of Figs. 8-12.

The whole experiment runs through the pure round engine: by default all
rounds execute as ONE compiled ``lax.scan`` program (``run_scanned``);
``--eager`` steps round by round instead (same trajectory, handy for
debugging / incremental output).

  PYTHONPATH=src python examples/hfl_mnist_train.py --rounds 10 [--non-iid]
                                                    [--policy fcea|gcea|rcea]
                                                    [--ddpg] [--full] [--eager]
"""
import argparse
import dataclasses

from repro import compile_cache
from repro.configs.hfl_mnist import CONFIG
from repro.core.hfl import HFLSimulation


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--policy", default="fcea",
                    choices=["fcea", "gcea", "rcea"])
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--oma", action="store_true")
    ap.add_argument("--ddpg", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="paper-faithful 64-client topology (slower)")
    ap.add_argument("--eager", action="store_true",
                    help="dispatch one jitted round at a time instead of "
                         "one scanned program for all rounds")
    ap.add_argument("--scenario", default="static",
                    help="dynamic-world preset (static, random_waypoint, "
                         "markov_dropout, hetero_devices, mobile_flaky, "
                         "full_dynamic, or a '+'-joined mixture)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = CONFIG if args.full else dataclasses.replace(
        CONFIG, n_clients=24, clients_per_edge=3, min_samples=80,
        max_samples=300, hidden=64, input_dim=196)
    sim = HFLSimulation(cfg, seed=args.seed, iid=not args.non_iid,
                        policy=args.policy, noma_enabled=not args.oma,
                        allocator="ddpg" if args.ddpg else "mid",
                        scenario=args.scenario)
    if args.ddpg:
        print("training DDPG allocator ...")
        hist = sim.train_ddpg(episodes=8, steps_per_episode=30, warmup=64)
        print("episode rewards:",
              [round(r, 2) for r in hist["episode_reward"]])

    print(f"policy={args.policy} noma={not args.oma} "
          f"iid={not args.non_iid} clients={cfg.n_clients} "
          f"scenario={args.scenario} "
          f"driver={'eager' if args.eager else 'scanned'}")
    ms = sim.run(args.rounds) if args.eager else sim.run_scanned(args.rounds)
    for m in ms:
        print(f"round {m.round:3d}  acc={m.accuracy:.4f}  loss={m.loss:.4f}  "
              f"avgMS={m.avg_staleness:.2f}  T={m.total_time_s:.2f}s  "
              f"E={m.total_energy_j:.1f}J  cost={m.cost:.2f}  "
              f"avail={m.n_available}  edges={m.z.astype(int).tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
