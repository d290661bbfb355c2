"""Serving benchmark — a mixed request trace through the gossip server.

Generates a synthetic trace (>= 2 topology fingerprints x >= 2
protocols x mixed replica counts), submits every request to one
`GossipServer` draining onto a factorized slot mesh
(`parallel.mesh.make_slot_mesh` over the host's 8 virtual CPU devices
by default, the real chips on TPU), and reports the serving headline:
**requests/s and p50/p99 turnaround under the mixed trace**, plus mean
slot occupancy.

Unless ``--no-verify``, every request's counters and coverage are then
re-derived by a solo ``batch/campaign`` run with the same seeds and
compared bitwise — the server's core contract (slot placement and batch
composition are semantically inert). A mismatch fails the run.

Emits exactly one JSON line on stdout (diagnostics on stderr);
scripts/ci_tier1.sh runs the ``--smoke`` trace and keeps that line.
Usage: python scripts/serve_bench.py [--requests 100] [--slots 8]
       [--devices 8] [--smoke] [--single-device] [--no-verify]
       [--seed 0] [--cpu] [--out FILE]
"""

import argparse
import json
import os
import sys
import time

# Self-locate the repo so the script runs from any cwd.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build_trace(requests: int, seed: int, smoke: bool) -> list[dict]:
    """Deterministic mixed trace: round-robin over (topology, protocol)
    scenario templates with replica counts cycling 1/2/4 and globally
    unique replica seeds (so every request is distinct work)."""
    n = 128 if smoke else 256
    topologies = [
        {"family": "erdos_renyi", "n": n, "p": 8.0 / n, "seed": 11},
        {"family": "watts_strogatz", "n": n, "k": 8, "beta": 0.1,
         "seed": 12},
    ]
    scenarios = []
    for topo in topologies:
        for proto in ("flood", "pushpull", "pushk"):
            scenarios.append({"topology": topo, "protocol": proto})
    # One lossy flood variant: a distinct static signature in the mix.
    scenarios.append({
        "topology": topologies[0], "protocol": "flood", "loss_prob": 0.05,
    })
    replica_cycle = (1, 2, 4)
    trace, next_seed = [], int(seed)
    for i in range(requests):
        sc = scenarios[i % len(scenarios)]
        reps = replica_cycle[i % len(replica_cycle)]
        trace.append({
            "request_id": f"req-{i:04d}",
            "shares": 4,
            "horizon": 16 if smoke else 24,
            "seeds": list(range(next_seed, next_seed + reps)),
            **sc,
        })
        next_seed += reps
    return trace


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual host device fan-out on CPU")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace: 12 requests, smaller graphs")
    ap.add_argument("--single-device", action="store_true",
                    help="skip the slot mesh; dispatch on one device")
    ap.add_argument("--no-verify", action="store_true",
                    help="skip the per-request solo bitwise comparison")
    ap.add_argument("--out", help="also append the JSON row to FILE")
    from p2p_gossip_tpu.utils.platform import add_cpu_arg

    add_cpu_arg(ap)
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 12)

    from p2p_gossip_tpu.utils.platform import (
        apply_cpu_arg,
        check_backend,
        enable_compile_cache,
        use_host_devices,
    )

    apply_cpu_arg(args)
    # Host run (--cpu / JAX_PLATFORMS=cpu): fan out virtual devices for
    # the slot mesh BEFORE jax loads. No chip otherwise: exit non-zero.
    use_host_devices(args.devices)
    check_backend()
    enable_compile_cache()

    import jax
    import numpy as np

    from p2p_gossip_tpu.parallel.mesh import make_slot_mesh
    from p2p_gossip_tpu.serve.server import GossipServer, matches_solo

    platform = jax.devices()[0].platform
    mesh = None
    mesh_shape = "1x1"
    if not args.single_device:
        mesh = make_slot_mesh(args.slots)
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        mesh_shape = f"{shape['replicas']}x{shape['nodes']}"
    log(f"serve_bench: {args.requests} requests, slots={args.slots}, "
        f"mesh={mesh_shape} on {platform}")

    trace = build_trace(args.requests, args.seed, args.smoke)
    server = GossipServer(slots=args.slots, mesh=mesh)

    t0 = time.perf_counter()
    for request_dict in trace:
        server.submit(request_dict)
    batches = server.drain()
    wall = time.perf_counter() - t0

    turnarounds = []
    for request_dict in trace:
        state = server._states[request_dict["request_id"]]
        if state.status != "done":
            log(f"serve_bench: request {request_dict['request_id']} "
                f"ended {state.status}")
            return 1
        turnarounds.append(state.turnaround_s)
    signatures = len({
        s.request.signature_key() for s in server._states.values()
    })
    log(f"serve_bench: drained {batches} batches "
        f"({signatures} signatures) in {wall:.2f}s, "
        f"occupancy {server.slot_occupancy():.3f}")

    bitwise_ok = None
    verified = 0
    if not args.no_verify:
        bitwise_ok = True
        for request_dict in trace:
            ok = matches_solo(server, request_dict["request_id"])
            verified += 1
            if not ok:
                bitwise_ok = False
                log(f"serve_bench: BITWISE MISMATCH on "
                    f"{request_dict['request_id']}")
        log(f"serve_bench: verified {verified}/{len(trace)} requests "
            f"vs solo campaign runs: "
            f"{'bitwise OK' if bitwise_ok else 'MISMATCH'}")

    row = {
        "bench": "serve",
        "platform": platform,
        "smoke": bool(args.smoke),
        "requests": len(trace),
        "signatures": signatures,
        "slots": args.slots,
        "mesh": mesh_shape,
        "batches": batches,
        "wall_s": round(wall, 3),
        "requests_per_s": round(len(trace) / wall, 3),
        "p50_turnaround_s": round(float(np.percentile(turnarounds, 50)), 4),
        "p99_turnaround_s": round(float(np.percentile(turnarounds, 99)), 4),
        "slot_occupancy": round(server.slot_occupancy(), 4),
        "verified": verified,
        "bitwise_ok": bitwise_ok,
    }
    line = json.dumps(row)
    print(line)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0 if bitwise_ok in (True, None) else 1


if __name__ == "__main__":
    sys.exit(main())
