"""Protocol comparison experiment: flood vs push-pull vs fanout push.

Runs the three protocols on the SAME graph and origins, and reports the
coverage/bandwidth trade-off each one makes — the experiment the
protocol family exists to support:

- flood (the reference's protocol, p2pnode.cc:127): fastest spread, one
  send per peer per processed share (~mean-degree sends per delivery);
- push-pull anti-entropy: guaranteed convergence, digest traffic every
  round whether or not anything is new;
- fanout push (rumor mongering): ~fanout sends per delivery, probabilistic
  coverage.

Usage: python scripts/protocol_compare.py [--nodes 2000] [--prob 0.005]
       [--shares 32] [--horizon 64] [--fanout 3] [--seed 0] [--json]

Prints a table (or one JSON line with --json); runs on the default JAX
device (set JAX_PLATFORMS=cpu to force CPU).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--prob", type=float, default=0.005)
    ap.add_argument("--shares", type=int, default=32)
    ap.add_argument("--horizon", type=int, default=64)
    ap.add_argument("--fanout", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--coverageFraction", type=float, default=0.99)
    ap.add_argument("--json", action="store_true")
    from p2p_gossip_tpu.utils.platform import (
        add_cpu_arg,
        apply_cpu_arg,
        check_backend,
        enable_compile_cache,
    )

    add_cpu_arg(ap)
    args = ap.parse_args()
    apply_cpu_arg(args)

    check_backend()
    enable_compile_cache()

    import p2p_gossip_tpu as pg
    from p2p_gossip_tpu.engine.sync import run_flood_coverage
    from p2p_gossip_tpu.models.generation import Schedule
    from p2p_gossip_tpu.models.protocols import run_pushk_sim, run_pushpull_sim
    from p2p_gossip_tpu.utils.analysis import (
        message_redundancy,
        propagation_latency,
    )

    g = pg.erdos_renyi(args.nodes, args.prob, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    origins = rng.integers(0, g.n, args.shares).astype(np.int32)
    sched = Schedule(g.n, origins, np.zeros(args.shares, dtype=np.int32))
    frac = args.coverageFraction

    def measure(name, run):
        t0 = time.perf_counter()
        stats, cov = run()
        wall = time.perf_counter() - t0
        red = message_redundancy(stats)
        # All shares generate at t=0, so latency-to-coverage IS
        # time-to-coverage — one computation serves both report fields.
        s = propagation_latency(cov, g.n, fractions=(frac,)).summary(frac)
        return {
            "protocol": name,
            "reached_fraction": s["reached"],
            "ttc_median_ticks": s["median"],
            "final_coverage_mean": float(cov[-1].mean()),
            "sends_per_delivery": (
                None
                if red["sends_per_delivery"] is None
                else round(red["sends_per_delivery"], 2)
            ),
            "total_sent": int(stats.sent.sum()),
            "p95_latency_ticks": s["p95"],
            "wall_s": round(wall, 3),
        }

    rows = [
        measure(
            "flood",
            lambda: run_flood_coverage(g, origins, args.horizon),
        ),
        measure(
            "pushpull",
            lambda: run_pushpull_sim(
                g, sched, args.horizon, seed=args.seed, record_coverage=True
            ),
        ),
        measure(
            "pull",
            lambda: run_pushpull_sim(
                g, sched, args.horizon, seed=args.seed, record_coverage=True,
                mode="pull",
            ),
        ),
        measure(
            f"pushk(k={args.fanout})",
            lambda: run_pushk_sim(
                g, sched, args.horizon, fanout=args.fanout, seed=args.seed,
                record_coverage=True,
            ),
        ),
    ]

    if args.json:
        print(json.dumps({"config": vars(args), "results": rows}))
        return
    cols = list(rows[0].keys())
    widths = [max(len(c), *(len(str(r[c])) for r in rows)) for c in cols]
    print(
        f"N={g.n} edges={g.num_edges} shares={args.shares} "
        f"horizon={args.horizon} target={frac:.0%}"
    )
    print("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    for r in rows:
        print("  ".join(str(r[c]).ljust(w) for c, w in zip(cols, widths)))


if __name__ == "__main__":
    main()
