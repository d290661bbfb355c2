"""Static-analysis gate for the compiled stack — one command, three analyzers.

    python scripts/staticcheck.py              # human report
    python scripts/staticcheck.py --json       # one JSON line on stdout
    python scripts/staticcheck.py --fixture f64|recompile|prng|
                                           telemetry|digest|exchange|
                                           meshfact|async
    python scripts/staticcheck.py --compile    # also lower+compile each
                                               # audited entry on the
                                               # default device (run it
                                               # on the chip)

Runs, in order: the AST lint (astlint — no jax needed), the jaxpr
invariant auditor over every registered entry point (jaxpr_audit), the
telemetry zero-cost check (telemetry_off — disabled metric rings must
compile away), and the recompile sentinel's replays (recompile): the
sweep-grid one and the serving scheduler's mixed request trace
(``run_serve_sentinel`` — one compile per distinct static signature
across backfilled slots). Exit code 1 iff
any analyzer reports a violation — which is also the ``--fixture``
contract: each seeded regression must keep exiting non-zero, and
tests/test_staticcheck.py asserts exactly that (a broken analyzer shows
up as the fixture exiting 0).

Wired into tier-1 by scripts/ci_tier1.sh (before pytest) and run by
tests/test_staticcheck.py. Diagnostics go to stderr;
stdout carries the report. Rule catalogue: docs/STATIC_ANALYSIS.md.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _setup_backend(need_jax: bool) -> None:
    """CPU-pinned runs get 8 virtual host devices (so the sharded audit
    specs stage a real 2x2 mesh) — before the first jax device query."""
    if not need_jax:
        return
    from p2p_gossip_tpu.utils.platform import use_host_devices

    use_host_devices(8)


def _compile_entries() -> dict:
    """Lower + compile every audited entry on the default device — the
    on-chip leg: an entry whose jaxpr audits clean can still fail XLA/
    Mosaic compilation on real hardware shapes. Returns per-entry
    status; never raises."""
    import jax

    from p2p_gossip_tpu.staticcheck import entrypoints, registry

    entrypoints.load_all()
    results, ok = [], True
    for entry in registry.all_entries():
        t0 = time.monotonic()
        try:
            spec = entry.spec()
            fn = spec.fn if spec.fn is not None else entry.fn
            jax.jit(
                lambda *args, _fn=fn, _kw=spec.kwargs: _fn(*args, **_kw)
            ).lower(*spec.args).compile()
            results.append({
                "entry": entry.name, "ok": True,
                "wall_s": round(time.monotonic() - t0, 2),
            })
        except Exception as e:
            ok = False
            results.append({
                "entry": entry.name, "ok": False,
                "error": f"{type(e).__name__}: {e}"[:500],
                "wall_s": round(time.monotonic() - t0, 2),
            })
    return {"ok": ok, "entries": results}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="one JSON line on stdout instead of the human report")
    ap.add_argument("--fixture",
                    choices=("f64", "recompile", "prng", "telemetry",
                             "digest", "exchange", "meshfact", "async",
                             "hub"),
                    help="run one seeded regression fixture; exits non-zero "
                    "iff the analyzer (correctly) flags it")
    ap.add_argument("--lint-only", action="store_true",
                    help="AST lint only — no jax tracing, ~2 s")
    ap.add_argument("--skip-sentinel", action="store_true",
                    help="skip the recompile sentinel's sweep replay")
    ap.add_argument("--compile", action="store_true",
                    help="additionally lower+compile each audited entry on "
                    "the default device (run it on the chip)")
    args = ap.parse_args()

    if args.fixture:
        _setup_backend(need_jax=args.fixture != "prng")
        from p2p_gossip_tpu.staticcheck.fixtures import run_fixture

        report = run_fixture(args.fixture)
        out = json.dumps(report) if args.json else "\n".join(
            [f"fixture {report['fixture']}: "
             + ("FLAGGED (expected)" if not report["ok"] else
                "NOT flagged — analyzer is blind to this regression")]
            + [f"  [{v.get('rule')}] {v.get('message')}"
               for v in report["violations"]]
        )
        print(out)
        return 0 if report["ok"] else 1

    _setup_backend(need_jax=not args.lint_only)
    report: dict = {}
    violations = 0
    t0 = time.monotonic()

    from p2p_gossip_tpu.staticcheck.astlint import run_lint

    lint = run_lint()
    report["lint"] = lint
    violations += len(lint["violations"])
    log(f"lint: {lint['files_scanned']} files, "
        f"{len(lint['violations'])} violation(s)")

    if not args.lint_only:
        if args.compile:
            # The compile leg targets the chip unless JAX_PLATFORMS=cpu
            # asked for the host; no chip otherwise means exit non-zero.
            from p2p_gossip_tpu.utils.platform import (
                check_backend,
                enable_compile_cache,
            )

            check_backend()
            enable_compile_cache()

        from p2p_gossip_tpu.staticcheck.jaxpr_audit import run_audit

        audit = run_audit()
        report["jaxpr"] = audit
        violations += len(audit["violations"])
        log(f"jaxpr audit: {audit['entries_audited']} entries, "
            f"{len(audit['violations'])} violation(s)")

        from p2p_gossip_tpu.staticcheck.telemetry_off import (
            run_telemetry_check,
        )

        tel = run_telemetry_check()
        report["telemetry"] = tel
        violations += len(tel["violations"])
        log(f"telemetry zero-cost: {tel['pairs_checked']} instrumented "
            f"pair(s), {len(tel['violations'])} violation(s)")

        if not args.skip_sentinel:
            from p2p_gossip_tpu.staticcheck.recompile import run_sentinel

            sentinel = run_sentinel()
            report["recompile"] = {
                **sentinel.as_dict(),
                "violations": [
                    {"rule": "recompile-sentinel", "message": m}
                    for m in sentinel.violations()
                ],
            }
            violations += len(sentinel.violations())
            log(f"recompile sentinel: {sentinel.cells} cells, "
                f"expected {sentinel.expected}, measured {sentinel.measured}")

            from p2p_gossip_tpu.staticcheck.recompile import (
                run_serve_sentinel,
            )

            serve_sentinel = run_serve_sentinel()
            report["serve_recompile"] = {
                **serve_sentinel.as_dict(),
                "violations": [
                    {"rule": "serve-recompile-sentinel", "message": m}
                    for m in serve_sentinel.violations()
                ],
            }
            violations += len(serve_sentinel.violations())
            log(f"serve sentinel: {serve_sentinel.cells} requests, "
                f"expected {serve_sentinel.expected}, "
                f"measured {serve_sentinel.measured}")

        if args.compile:
            import jax

            comp = _compile_entries()
            report["compile"] = comp
            report["platform"] = jax.devices()[0].platform
            if not comp["ok"]:
                violations += sum(
                    1 for r in comp["entries"] if not r["ok"]
                )
            log(f"compile: {sum(r['ok'] for r in comp['entries'])}/"
                f"{len(comp['entries'])} entries compiled clean on "
                f"{report['platform']}")

    report["ok"] = violations == 0
    report["violations_total"] = violations
    report["wall_s"] = round(time.monotonic() - t0, 2)

    if args.json:
        print(json.dumps(report))
    else:
        print(f"staticcheck: {'OK' if report['ok'] else 'FAIL'} "
              f"({violations} violation(s), {report['wall_s']}s)")
        for section in ("lint", "jaxpr", "telemetry", "recompile",
                        "serve_recompile", "compile"):
            sec = report.get(section)
            if not sec:
                continue
            for v in sec.get("violations", []):
                loc = f"{v.get('file')}:{v.get('line')}: " if "file" in v \
                    else (f"{v['entry']}: " if "entry" in v else "")
                print(f"  {loc}[{v.get('rule')}] {v.get('message')}")
            if section == "compile":
                for r in sec.get("entries", []):
                    if not r["ok"]:
                        print(f"  {r['entry']}: [compile] {r['error']}")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
