"""On-chip Pallas-vs-XLA kernel bake-off (VERDICT round-1 item #3).

Measures, on the real TPU, each candidate kernel against its XLA
formulation at bench-relevant shapes, asserting bitwise parity before
timing:

1. coverage_per_slot   — Pallas one-pass kernel vs the jnp bit-expansion
                         (row sweep doubles as the 1M-crash bisection;
                         the fused tick-update kernels that used to be
                         benched between 1 and 2 lost on hardware and
                         were deleted — see docs/RESULTS.md)
2. gather-OR frontier  — the XLA blocked-gather path at several degree
                         blocks (the Pallas rejection arithmetic for a
                         per-edge-DMA formulation is printed alongside:
                         it is not implemented because its descriptor
                         count is prohibitive — see the JSON notes)

Timing discipline: every measurement chains ``iters`` dependent
applications on-device and forces ONE reduced scalar to the host at the
end, so per-call dispatch overhead is amortized over the chain.

Output: one JSON object per line on stdout; progress to stderr.
Usage: python scripts/kernel_bench.py [--rows 100000] [--words 256]
       [--sweep]   (adds the 250K/500K/1M coverage-row bisection)
"""

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


#: Stamped into every emitted row once the device is known: CPU rows run
#: Pallas in interpret mode, so their "speedup" numbers are meaningless
#: for the TPU bake-off — they must never be mistaken for on-chip rows.
_ROW_TAG: dict = {}


def emit(**row):
    print(json.dumps({**_ROW_TAG, **row}), flush=True)


def chain_time(fn, x, iters=20):
    """Wall time per op over ``iters`` chained dependent applications,
    forced once via host transfer of a reduction."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chained(x):
        for _ in range(iters):
            x = fn(x)
        return jnp.sum(x[..., :1])

    np.asarray(chained(x))  # compile + warm
    t0 = time.perf_counter()
    np.asarray(chained(x))
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--words", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument(
        "--sweep", action="store_true",
        help="row sweep 250K/500K/1M for the coverage kernel (the round-1 "
        "worker-crash bisection); run each under its own process if a "
        "row size may crash the worker",
    )
    ap.add_argument(
        "--skip-gather", action="store_true",
        help="skip the gather timing (needs a 100K-node graph build)",
    )
    ap.add_argument(
        "--cache", default="",
        help="npz graph cache for the gather graph (scale_1m.py "
        "fingerprint scheme); the RCM permutation persists alongside it "
        "as an aux array, so the host-side reordering runs once per "
        "graph build instead of once per bench invocation",
    )
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
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    log(f"device: {dev}")
    on_tpu = dev.platform == "tpu"
    interpret = not on_tpu
    _ROW_TAG["platform"] = dev.platform
    if interpret:
        _ROW_TAG["interpret_mode"] = True

    from p2p_gossip_tpu.ops import bitmask
    from p2p_gossip_tpu.ops.pallas_kernels import coverage_per_slot_pallas

    rng = np.random.default_rng(0)

    def rand_bits(n, w):
        return jnp.asarray(
            rng.integers(0, 2**32, size=(n, w), dtype=np.uint64).astype(
                np.uint32
            )
        )

    # --- 1. coverage kernel --------------------------------------------
    row_list = [args.rows] + ([250_000, 500_000, 1_000_000] if args.sweep else [])
    slots = args.words * 32
    for n in row_list:
        seen = rand_bits(n, args.words)
        want = np.asarray(bitmask.coverage_per_slot(seen, slots))
        got = np.asarray(
            coverage_per_slot_pallas(seen, slots, interpret=interpret)
        )
        assert np.array_equal(want, got), f"coverage parity FAILED at N={n}"
        t_xla = _time_cov(
            lambda s: bitmask.coverage_per_slot(s, slots), seen, args.iters
        )
        t_pal = _time_cov(
            lambda s: coverage_per_slot_pallas(s, slots, interpret=interpret),
            seen, args.iters,
        )
        log(f"coverage N={n}: xla {t_xla*1e3:.2f} ms  pallas {t_pal*1e3:.2f} ms")
        emit(
            kernel="coverage_per_slot", rows=n, words=args.words,
            xla_ms=round(t_xla * 1e3, 3), pallas_ms=round(t_pal * 1e3, 3),
            speedup=round(t_xla / t_pal, 3), parity="ok",
        )

    # --- 2. (removed) fused tick update ------------------------------
    # The fused Pallas tick-update kernels were benched on hardware by
    # the round-4 battery (kernel stage, 2026-07-31): tick_update lost
    # 0.50x and tick_update+coverage 0.60x against the fused XLA graph
    # at 100K x 256 words — XLA already fuses the arrivals->newly->seen->
    # popcount chain better than the hand tiling. Per the enable-or-
    # delete rule the kernels are gone; the A/B rows live in
    # docs/RESULTS.md.
    emit(
        kernel="tick_update", status="removed",
        note="lost 0.50x on hardware vs fused XLA (round-4 battery); "
        "kernel deleted, XLA path is the product path",
    )
    emit(
        kernel="tick_update_cov", status="removed",
        note="lost 0.60x on hardware vs fused XLA (round-4 battery); "
        "kernel deleted, XLA path is the product path",
    )

    # --- 3. gather-OR (XLA path + the Pallas rejection arithmetic) -----
    if not args.skip_gather:
        import p2p_gossip_tpu as pg
        from p2p_gossip_tpu.engine.sync import DeviceGraph
        from p2p_gossip_tpu.ops.ell import propagate_bucketed

        from p2p_gossip_tpu.models.topology import (
            load_or_build_graph_cache,
        )

        g_rows = min(args.rows, 100_000)
        g = load_or_build_graph_cache(
            args.cache, topology="er", nodes=g_rows, prob=0.001, ba_m=3,
            seed=0, build=lambda: pg.erdos_renyi(g_rows, 0.001, seed=0),
            log=log,
        )
        # bucketed=True unconditionally: small --rows smoke runs fall
        # under the auto threshold but must exercise the same path.
        dg = DeviceGraph.build(g, bucketed=True)
        w = args.words
        hist = rand_bits(2 * g.n, w).reshape(2, g.n, w)
        edges = int(np.asarray(dg.degree).sum())

        def make_gather(blk, dg_=dg, n_out=g.n):
            def gather(h):
                arr = propagate_bucketed(
                    h[0][None], jnp.int32(1), dg_.buckets, n_out=n_out,
                    ring_size=1, uniform_delay=0, block=blk,
                )
                return h ^ arr[None]
            return gather

        # 128 rides along to test whether the round-1 sweep (which chose
        # 64 from {8,16,32,64}) stopped short of the optimum.
        for blk in (8, 32, 64, 128):
            t = chain_time(make_gather(blk), hist, max(args.iters // 2, 5))
            log(f"gather block={blk}: {t*1e3:.2f} ms/tick")
            emit(
                kernel="gather_or_xla", rows=g.n, words=w, block=blk,
                ms_per_tick=round(t * 1e3, 3),
                gathered_gb=round(edges * w * 4 / 1e9, 2),
                achieved_gbps=round(edges * w * 4 / t / 1e9, 1),
            )
        # Why no Pallas gather: a per-edge DMA formulation issues one
        # descriptor per (edge, W-word row); at ~1 us/descriptor issue+
        # latency that alone exceeds the XLA gather's whole-tick time by
        # orders of magnitude.
        frontier_mb = g.n * w * 4 / 1e6
        vmem_note = (
            f"frontier ({g.n}x{w}x4B = {frontier_mb:.1f} MB) cannot be "
            "VMEM-resident (16 MB), so a dense in-VMEM gather is impossible"
            if frontier_mb > 16
            else f"frontier is only {frontier_mb:.1f} MB at this smoke "
            "shape (bench shapes exceed VMEM)"
        )
        emit(
            kernel="gather_or_pallas_rejection", rows=g.n, edges=edges,
            note=(
                "per-edge DMA formulation rejected by arithmetic: "
                f"{edges} descriptors x ~1us >> XLA gather tick; " + vmem_note
            ),
        )

        # Word-width sweep at the tuned block: measures the lane-underfill
        # penalty the MIN_CHUNK_SHARES comment quotes (~15x worse bytes/s
        # at 32 words vs 128, round-1 measurement). The resident-HBM
        # auto-chunk (scale_1m.py) halves the pad to 64 words at the 1M
        # shape, so the 64-vs-128 ratio is exactly the bandwidth price of
        # fitting — worth a measured row, not a two-generations-old quote.
        # All four widths are emitted (the default width repeats its
        # block-sweep measurement) so this table is self-contained.
        for ww in (32, 64, 128, 256):
            hist_w = rand_bits(2 * g.n, ww).reshape(2, g.n, ww)
            t = chain_time(make_gather(64), hist_w, max(args.iters // 2, 5))
            log(f"gather words={ww}: {t*1e3:.2f} ms/tick")
            emit(
                kernel="gather_or_xla_wsweep", rows=g.n, words=ww, block=64,
                ms_per_tick=round(t * 1e3, 3),
                gathered_gb=round(edges * ww * 4 / 1e9, 2),
                achieved_gbps=round(edges * ww * 4 / t / 1e9, 1),
            )

        # RCM-relabeled gather: does clustering neighborhoods in node-id
        # space (= HBM address space for the frontier rows) buy gather
        # bandwidth? Same edges, same degree multiset, bitwise-equal
        # dynamics (tests/test_topology.py) — only the id layout differs.
        # On this ER expander RCM cannot reduce bandwidth much in theory;
        # this row measures what locality is actually worth on the chip
        # before investing in reorder-aware staging.
        try:
            from p2p_gossip_tpu.models.topology import (
                load_or_compute_graph_aux,
                rcm_order,
                relabel_graph,
                scale_graph_fingerprint,
            )

            # The permutation is a pure function of the graph, so it
            # rides the same npz under the build fingerprint and the
            # host-side RCM pass runs once per graph build.
            order = load_or_compute_graph_aux(
                args.cache, "rcm",
                scale_graph_fingerprint("er", g_rows, 0.001, 3, 0),
                lambda: rcm_order(g), log,
            )
            rg, _inv = relabel_graph(g, order)
        except ImportError as e:  # rcm_order needs scipy (optional dep)
            emit(kernel="gather_or_xla_rcm", rows=g.n,
                 note=f"skipped: {e}")
        else:
            dg_r = DeviceGraph.build(rg, bucketed=True)
            t = chain_time(
                make_gather(64, dg_r, rg.n), hist, max(args.iters // 2, 5)
            )
            log(f"gather rcm block=64: {t*1e3:.2f} ms/tick")
            emit(
                kernel="gather_or_xla_rcm", rows=g.n, words=w, block=64,
                ms_per_tick=round(t * 1e3, 3),
                gathered_gb=round(edges * w * 4 / 1e9, 2),
                achieved_gbps=round(edges * w * 4 / t / 1e9, 1),
            )


def _time_cov(fn, seen, iters):
    """Coverage returns (S,) int32 — chain by folding back into uint32."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chained(s):
        acc = jnp.int32(0)
        for _ in range(iters):
            cov = fn(s)
            acc = acc + cov[0]
            s = s ^ acc.astype(jnp.uint32)  # data dependence
        return acc

    np.asarray(chained(seen))
    t0 = time.perf_counter()
    np.asarray(chained(seen))
    return (time.perf_counter() - t0) / iters


if __name__ == "__main__":
    main()
